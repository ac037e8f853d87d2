"""Operations and bytes the looped LM's step and its attention kernels NEED,
from shapes: what the algorithm has to do, not what an implementation happens
to do (no recomputation, no masked-out work). Causal throughout: a query sees
``T (T + 1) / 2`` keys in all, not ``T x T``."""

from __future__ import annotations


def _causal_pairs(t: int) -> int:
    return t * (t + 1) // 2


def flash_fwd(batch: int, heads: int, t: int, head_dim: int, itemsize: int) -> dict:
    """One forward call over [batch, heads, t, head_dim]: QK^T and PV, each
    2 x head_dim operations per (query, key) pair that causality keeps.
    Reads q, k, v once, writes o once and the two float32 row statistics."""
    rows = batch * heads
    return {
        "flops": rows * 4 * head_dim * _causal_pairs(t),
        "bytes": rows * (4 * t * head_dim * itemsize + 2 * t * 4),
    }


def flash_bwd(batch: int, heads: int, t: int, head_dim: int, itemsize: int) -> dict:
    """The backward pass of one such call (the dq and the dk/dv kernel
    together): dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q, four
    products of 2 x head_dim operations a kept pair. Recomputing the scores
    (which this repo's two kernels each do) is not needed work. Reads q, k,
    v, dO and the two row statistics once, writes dq, dk, dv once."""
    rows = batch * heads
    return {
        "flops": rows * 8 * head_dim * _causal_pairs(t),
        "bytes": rows * (7 * t * head_dim * itemsize + 2 * t * 4),
    }


def layer_params(hidden: int, intermediate: int) -> int:
    """Matrix parameters of one layer: q, k, v, o and the SwiGLU's three."""
    return 4 * hidden * hidden + 3 * hidden * intermediate


def looplm_step_flops(
    batch: int, t: int, hidden: int, intermediate: int, layers: int,
    loop_steps: int, vocab: int,
) -> dict:
    """Model FLOPs of one training step of the looped LM, forward + backward
    = 3 x forward, every layer counted ``loop_steps`` times, one head per
    exit, causal attention; recomputation does not count. The parts:
    ``layers`` (6 x parameters x tokens x applications), ``heads``,
    ``attention`` (forward 4 x hidden per kept pair, over all heads)."""
    tokens = batch * t
    parts = {
        "layers": 6 * layer_params(hidden, intermediate) * tokens
        * layers * loop_steps,
        "heads": 6 * hidden * vocab * tokens * loop_steps,
        "attention": 3 * 4 * hidden * _causal_pairs(t) * batch
        * layers * loop_steps,
    }
    parts["total"] = sum(parts.values())
    return parts
