"""Where Pallas kernels compile: the one rule every op in this package asks.

Mosaic (the Pallas TPU compiler) only exists behind the TPU backend. On any
other backend the same kernel bodies run through the Pallas interpreter, so
the CPU test suite exercises the identical code — but an interpreted kernel
on a TPU would be a silent stand-in, so ``None`` never resolves to
interpret there. ``chip_smoke.py`` asserts the platform before it trusts
this rule.

XLA cannot partition a Mosaic call. Under a mesh a kernel therefore runs per
shard (``per_shard``: each device's own call on its rows of the batch); with
several devices and NO mesh (``unpartitioned``) a kernel that has another
form falls back to it and one that has none refuses.
"""

from __future__ import annotations

import jax

# what a call may ask for of a core's 128 MiB of VMEM through
# ``vmem_limit_bytes``
VMEM_ASK_BOUND_BYTES = 96 * 2**20
# mesh axes that may split a kernel's batch under a mesh
BATCH_AXES = ("data", "dp", "batch")


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pallas_interpret(interpret: bool | None = None) -> bool:
    """Resolve a kernel's ``interpret`` argument: an explicit value wins
    (tests pin ``True``); ``None`` means compiled on TPU, interpreted
    everywhere else."""
    return (not on_tpu()) if interpret is None else bool(interpret)


def active_mesh():
    """The mesh governing the current trace (``jax.set_mesh``), or None when
    no mesh context is active."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.shape else None


def unpartitioned() -> bool:
    """Several devices and no mesh (a multi-device jit in the plain
    ``in_shardings`` style): a Mosaic call would go to XLA's partitioner,
    which raises ``NotImplementedError``."""
    return active_mesh() is None and jax.device_count() > 1


def per_shard(fn, mesh, specs, batch_axes=BATCH_AXES):
    """``fn`` as each device's own call on its shard: ``specs`` gives (in,
    out) ``PartitionSpec``s from the names of ``batch_axes`` that split the
    batch in ``mesh`` (None where none does); any other axis sees replicated
    data."""
    present = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    in_specs, out_specs = specs(present or None)
    # the pallas interpreter can't reconcile invariant grid slices with
    # varying operands; numerics are test-validated against the plain forms
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def per_shard_under_mesh(fn, specs):
    """``per_shard(fn, <the active mesh>, specs)`` where a mesh is active and
    splits anything; ``fn`` itself on one device or with no mesh."""
    mesh = active_mesh()
    if mesh is not None and any(n > 1 for n in mesh.shape.values()):
        return per_shard(fn, mesh, specs)
    return fn
