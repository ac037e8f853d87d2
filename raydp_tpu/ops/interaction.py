"""DLRM dot-interaction op.

The pairwise-feature-interaction at the heart of DLRM (the reference ships it
inside the pytorch_dlrm notebook's model as a python loop over torch ops): for
stacked per-feature embeddings T = [B, F, D], compute all pairwise dot
products and return the strict lower triangle, [B, F*(F-1)/2].

Two paths:
- ``dot_interaction``: XLA einsum + static gather — lowers to one batched MXU
  matmul; the fallback and autodiff path.
- ``dot_interaction_pallas``: fused pallas kernel (batch-tiled; keeps T in
  VMEM, runs the F×F Gram matmul on the MXU, selects the triangle in-register
  and writes only the packed output). Runs ``interpret=True`` off-TPU so tests
  exercise the same kernel on the CPU mesh.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from raydp_tpu.ops.backend import pallas_interpret


def _tril_indices(f: int):
    rows, cols = np.tril_indices(f, k=-1)
    return rows.astype(np.int32), cols.astype(np.int32)


def dot_interaction(stacked: jnp.ndarray) -> jnp.ndarray:
    """[B, F, D] -> [B, F*(F-1)/2] pairwise dots (XLA path)."""
    gram = jnp.einsum("bfd,bgd->bfg", stacked, stacked)
    rows, cols = _tril_indices(stacked.shape[1])
    return gram[:, rows, cols]


def _interaction_kernel(t_ref, out_ref):
    t = t_ref[:]  # [BB, F, D]
    gram = jax.lax.dot_general(
        t, t, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # [BB, F, F] — one batched MXU matmul
    f = t.shape[1]
    # pack the strict lower triangle with static slices (F is small and
    # static, so this unrolls; no dynamic gather, which pallas disallows)
    offset = 0
    for i in range(1, f):
        out_ref[:, offset : offset + i] = gram[:, i, :i].astype(out_ref.dtype)
        offset += i


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def dot_interaction_pallas(
    stacked: jnp.ndarray, block_batch: int = 128, interpret: bool | None = None
) -> jnp.ndarray:
    """Fused pallas version (1.4-1.5x the XLA path at Criteo scale on v5e).
    Falls back to interpret mode off-TPU. Differentiable: the backward pass
    scatters the packed cotangent back into the symmetric Gram gradient."""
    return _interaction_forward(stacked, block_batch, interpret)


def _interaction_fwd(stacked, block_batch, interpret):
    return _interaction_forward(stacked, block_batch, interpret), stacked


def _interaction_bwd(block_batch, interpret, stacked, g):
    b, f, d = stacked.shape
    rows, cols = _tril_indices(f)
    gram_grad = jnp.zeros((b, f, f), g.dtype)
    gram_grad = gram_grad.at[:, rows, cols].set(g)
    sym = gram_grad + jnp.swapaxes(gram_grad, 1, 2)  # d(T Tᵀ) is symmetric
    return (jnp.einsum("bfg,bgd->bfd", sym, stacked),)


dot_interaction_pallas.defvjp(_interaction_fwd, _interaction_bwd)


def _active_mesh():
    """The mesh governing the current trace (``jax.set_mesh``), or None when
    no mesh context is active."""
    mesh = jax.sharding.get_abstract_mesh()
    return mesh if mesh.shape else None


def dot_interaction_fused(
    stacked: jnp.ndarray,
    batch_axes: Sequence[str] = ("data", "dp", "batch"),
    block_batch: int = 128,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """The pallas interaction kernel, runnable under MULTI-DEVICE jit.

    Mosaic kernels cannot be auto-partitioned by XLA, so under a multi-device
    mesh the kernel is wrapped in ``shard_map`` over the batch axes (the
    op is embarrassingly parallel in B): each device runs the fused kernel on
    its local [B/dp, F, D] shard and the surrounding jit keeps dp×tp layouts
    untouched. Single-device (or no active mesh) falls through to the plain
    pallas call. ``batch_axes`` lists mesh-axis names that may shard B; any
    other axes see replicated data."""
    mesh = _active_mesh()
    if mesh is None:
        if jax.device_count() > 1:
            # a multi-device jit with NO mesh context (plain in_shardings
            # style) would hand the Mosaic kernel to the auto-partitioner,
            # which raises NotImplementedError — use the einsum path there
            return dot_interaction(stacked)
        return dot_interaction_pallas(stacked, block_batch, interpret)
    if int(np.prod(list(mesh.shape.values()))) == 1:
        return dot_interaction_pallas(stacked, block_batch, interpret)
    from jax.sharding import PartitionSpec as P

    present = tuple(a for a in batch_axes if mesh.shape.get(a, 1) > 1)
    fn = jax.shard_map(
        partial(dot_interaction_pallas, block_batch=block_batch, interpret=interpret),
        mesh=mesh,
        in_specs=P(present if present else None, None, None),
        out_specs=P(present if present else None, None),
        # the pallas interpreter can't reconcile invariant grid slices with
        # varying operands; numerics are test-validated against the einsum
        check_vma=False,
    )
    return fn(stacked)


def _interaction_forward(
    stacked: jnp.ndarray, block_batch: int = 128, interpret: bool | None = None
) -> jnp.ndarray:
    from jax.experimental import pallas as pl

    interpret = pallas_interpret(interpret)
    b, f, d = stacked.shape
    out_f = f * (f - 1) // 2
    block_batch = min(block_batch, b)
    if b % block_batch:
        # pad batch so the grid divides evenly (static shapes for the MXU)
        pad = block_batch - b % block_batch
        stacked = jnp.concatenate(
            [stacked, jnp.zeros((pad, f, d), stacked.dtype)], axis=0
        )
    padded_b = stacked.shape[0]
    grid = (padded_b // block_batch,)
    out = pl.pallas_call(
        _interaction_kernel,
        out_shape=jax.ShapeDtypeStruct((padded_b, out_f), stacked.dtype),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_batch, f, d), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_batch, out_f), lambda i: (i, 0)),
        interpret=interpret,
        name="dlrm_interaction",
    )(stacked)
    return out[:b]
