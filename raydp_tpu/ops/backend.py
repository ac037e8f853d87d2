"""Where Pallas kernels compile: the one rule every op in this package asks.

Mosaic (the Pallas TPU compiler) only exists behind the TPU backend. On any
other backend the same kernel bodies run through the Pallas interpreter, so
the CPU test suite exercises the identical code — but an interpreted kernel
on a TPU would be a silent stand-in, so ``None`` never resolves to
interpret there. ``chip_smoke.py`` asserts the platform before it trusts
this rule.
"""

from __future__ import annotations

import jax


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument: an explicit value wins
    (tests pin ``True``); ``None`` means compiled on TPU, interpreted
    everywhere else."""
    return (not on_tpu()) if interpret is None else bool(interpret)
