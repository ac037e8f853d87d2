"""Operations and bytes a kernel's call NEEDS, from its shapes: what the
algorithm has to do, not what an implementation happens to do. A roofline
share built on these cannot pass 100 % unless the time leaves work out."""

from __future__ import annotations

from typing import Sequence


def dot_interaction(batch: int, features: int, dim: int, itemsize: int) -> dict:
    """Pairwise dots of ``features`` vectors of length ``dim`` per row,
    strict lower triangle: F(F-1)/2 dots, each ``dim`` multiply-adds. Reads
    the stacked embeddings once, writes the packed triangle once."""
    pairs = features * (features - 1) // 2
    return {
        "flops": 2 * batch * pairs * dim,
        "bytes": batch * features * dim * itemsize + batch * pairs * itemsize,
    }


def dlrm_step_flops(
    batch: int, num_dense: int, embed_dim: int, bottom_mlp: Sequence[int],
    top_mlp: Sequence[int], num_tables: int,
) -> float:
    """Model FLOPs of one DLRM training step (forward + backward = 3x the
    forward's matmul FLOPs; embedding lookups and the optimizer are bytes,
    not FLOPs)."""
    widths = [num_dense, *bottom_mlp, embed_dim]
    fwd = sum(2 * a * b for a, b in zip(widths, widths[1:]))
    features = num_tables + 1
    pairs = features * (features - 1) // 2
    fwd += 2 * pairs * embed_dim
    widths = [embed_dim + pairs, *top_mlp, 1]
    fwd += sum(2 * a * b for a, b in zip(widths, widths[1:]))
    return 3.0 * batch * fwd


def roofline(cost: dict, peaks: dict) -> dict:
    """Least time the chip could take for ``cost`` and which bound sets it."""
    t_flops = cost["flops"] / peaks["flops_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return {
        "min_s": max(t_flops, t_bytes),
        "bound": "flops" if t_flops >= t_bytes else "bytes",
    }
