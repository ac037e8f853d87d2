"""DLRM dot-interaction op.

The pairwise-feature-interaction at the heart of DLRM (the reference ships it
inside the pytorch_dlrm notebook's model as a python loop over torch ops): for
a sample's ``F`` feature vectors of ``D`` numbers, all pairwise dot products,
the strict lower triangle packed row by row: ``[B, F*(F-1)/2]``.

The operand is FEATURE-MAJOR, the batch on the lanes: ``[F, D, B]``. On the
chip a ``[B, D]`` block of rows (D = 16) pads its 16 columns to the 128
lanes, eight times its bytes, and as one of ``F`` blocks of a ``[B, F, D]``
operand it is a ``[B, 1, D]`` array first, which pads 8- to 128-fold (the
size-1 axis on the sublanes or on the lanes): a DLRM step spent 1.46 of its
3.9 ms building and slicing such blocks (PERF.md, Findings, PR 47). A
``[D, B]`` slab is whole tiles, sixteen lane tiles by ``D / 8`` sublane
tiles with nothing padded, stacking ``F`` of them along a leading axis moves
no tile, and it is the form the row kernels' results and the tables
themselves have on the chip (``ops/row_gather.py``).

- ``interaction_xla`` / ``dot_interaction``: XLA, the fallback path (an einsum
  and a static gather), for the feature-major and for a ``[B, F, D]``
  operand.
- ``interaction_pallas``: the Mosaic kernel ``dlrm_interaction`` on the
  feature-major operand, a tile of lanes a grid step: the tile is turned in
  VMEM into ``[bb, F, D]`` (where the padding costs no HBM traffic), every
  sample's Gram matrix is one batched MXU product at the precision of the
  trace's context, the triangle is packed in registers and only
  ``[B, F*(F-1)/2]`` is written. Its backward is ``jnp`` on ``[F, D, B]``.
  Runs ``interpret=True`` off-TPU so tests exercise the same kernel on the
  CPU.
- ``interaction_fused``: the kernel where it can run (per shard under a mesh,
  the batch axis LAST in its specs), XLA where not (:func:`supports`).
- ``dot_interaction_pallas`` / ``dot_interaction_fused``: the same for a
  ``[B, F, D]`` operand, transposed on the way in.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.sharding import PartitionSpec as P

from raydp_tpu.ops.backend import (
    BATCH_AXES, active_mesh, pallas_interpret, per_shard, unpartitioned)

LANES, SUBLANES = 128, 8
# samples a grid step of the kernel (whole lane tiles): a tile's batch-major
# form and its Gram matrices are padded to (8, 128) tiles in VMEM, 2 MB each
# at 128 samples (chip runs by tile: PERF.md Findings, PR 47)
BLOCK_BATCH = 128


def _tril_indices(f: int):
    rows, cols = np.tril_indices(f, k=-1)
    return rows.astype(np.int32), cols.astype(np.int32)


def _triangle(gram: jnp.ndarray) -> jnp.ndarray:
    """[B, F, F] -> its strict lower triangle, row by row."""
    rows, cols = _tril_indices(gram.shape[1])
    return gram[:, rows, cols]


def dot_interaction(stacked: jnp.ndarray) -> jnp.ndarray:
    """[B, F, D] -> [B, F*(F-1)/2] pairwise dots (XLA path)."""
    return _triangle(jnp.einsum("bfd,bgd->bfg", stacked, stacked))


def interaction_xla(slabs: jnp.ndarray) -> jnp.ndarray:
    """[F, D, B] -> [B, F*(F-1)/2] pairwise dots (XLA path)."""
    return _triangle(jnp.einsum("fdb,gdb->bfg", slabs, slabs))


def supports(dim: int, dtype) -> str:
    """Why a ``[F, dim, B]`` operand of this dtype cannot go through the
    kernel; empty if it can. A ``[dim, bb]`` slab of the block has to be
    whole sublane tiles: 8 rows of 4 bytes, 16 of 2."""
    dtype = jnp.dtype(dtype)
    if not jnp.issubdtype(dtype, jnp.floating) or dtype.itemsize not in (2, 4):
        return f"a {dtype.name} operand"
    rows = SUBLANES * 4 // dtype.itemsize
    if dim % rows:
        return f"{dtype.name} vectors of {dim} (not a multiple of {rows})"
    return ""


def _interaction_kernel(t_ref, out_ref):
    """A tile of lanes of the feature-major operand, turned in VMEM (exact)
    into the batch-major form the MXU wants, then the Gram of every sample
    as ONE batched product, at the precision of the trace's context: the
    arithmetic, and so the bits, of the ``[B, F, D]`` kernel this replaces
    and of XLA's einsum (PERF.md, Findings, PR 47: a forward that differs in
    the last place flips a ReLU at its kink now and then)."""
    t = jnp.transpose(t_ref[...].astype(jnp.float32), (2, 0, 1))  # [bb, F, D]
    gram = lax.dot_general(
        t, t, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )  # [bb, F, F]
    # pack the strict lower triangle with static slices (F is small and
    # static, so this unrolls; no dynamic gather, which pallas disallows)
    offset = 0
    for i in range(1, t.shape[1]):
        out_ref[:, offset:offset + i] = gram[:, i, :i].astype(out_ref.dtype)
        offset += i


def _interaction_forward(slabs, block_batch, interpret):
    f, d, b = slabs.shape
    pairs = f * (f - 1) // 2
    why = supports(d, slabs.dtype)
    if why:
        raise ValueError(f"the interaction kernel does not take {why}")
    # a tile of lanes a grid step; a batch that fills no tile is padded
    block = -(-min(block_batch or BLOCK_BATCH, b) // LANES) * LANES
    padded = -(-b // block) * block
    if padded != b:
        slabs = jnp.pad(slabs, ((0, 0), (0, 0), (0, padded - b)))
    out = pl.pallas_call(
        _interaction_kernel,
        grid=(padded // block,),
        in_specs=[pl.BlockSpec((f, d, block), lambda i: (0, 0, i))],
        out_specs=pl.BlockSpec((block, pairs), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, pairs), slabs.dtype),
        interpret=pallas_interpret(interpret),
        name="dlrm_interaction",
    )(slabs)
    return out[:b]


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def interaction_pallas(
    slabs: jnp.ndarray, block_batch: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """[F, D, B] -> [B, F*(F-1)/2]: the Mosaic kernel, one call a forward
    pass; interpreted off-TPU. ``block_batch``: samples a grid step, rounded
    up to whole lane tiles (None: ``BLOCK_BATCH``). Differentiable: the
    cotangent comes back feature-major too."""
    return _interaction_forward(slabs, block_batch, interpret)


def _interaction_fwd(slabs, block_batch, interpret):
    return _interaction_forward(slabs, block_batch, interpret), slabs


def _interaction_bwd(block_batch, interpret, slabs, g):
    """d(slabs)[i] = sum over j of g[pair(i, j)] * slabs[j], the batch on the
    lanes throughout: ``g``'s rows once transposed, a pair's row of it
    broadcast over the ``D`` sublanes of slab ``j``."""
    f = slabs.shape[0]
    rows, cols = _tril_indices(f)
    pair = np.full((f, f), len(rows), np.int32)  # the diagonal: a row of 0
    pair[rows, cols] = pair[cols, rows] = np.arange(len(rows))
    gt = jnp.pad(g.T.astype(jnp.float32), ((0, 1), (0, 0)))  # [pairs + 1, B]
    sym = gt[pair]  # [F, F, B]
    grad = (sym[:, :, None, :] * slabs[None].astype(jnp.float32)).sum(1)
    return (grad.astype(slabs.dtype),)


interaction_pallas.defvjp(_interaction_fwd, _interaction_bwd)


def dot_interaction_pallas(
    stacked: jnp.ndarray, block_batch: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """:func:`interaction_pallas` for a ``[B, F, D]`` operand."""
    return interaction_pallas(
        jnp.transpose(stacked, (1, 2, 0)), block_batch, interpret)


def interaction_fused(
    slabs: jnp.ndarray,
    batch_axes: Sequence[str] = BATCH_AXES,
    block_batch: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """The kernel on ``[F, D, B]``, runnable under MULTI-DEVICE jit.

    Mosaic kernels cannot be auto-partitioned by XLA, so under a multi-device
    mesh the kernel is wrapped in ``shard_map`` over the batch axes (the op
    is embarrassingly parallel in B, the operand's LAST axis): each device
    runs the fused kernel on its local [F, D, B/dp] shard and the surrounding
    jit keeps dp×tp layouts untouched. Single-device (or no active mesh)
    falls through to the plain pallas call. ``batch_axes`` lists mesh-axis
    names that may shard B; any other axes see replicated data. Where the
    kernel cannot run (:func:`supports`, or several devices and no mesh) this
    is the einsum."""
    mesh = active_mesh()
    if supports(slabs.shape[1], slabs.dtype) or unpartitioned():
        return interaction_xla(slabs)
    if mesh is None or int(np.prod(list(mesh.shape.values()))) == 1:
        return interaction_pallas(slabs, block_batch, interpret)
    return per_shard(
        partial(interaction_pallas, block_batch=block_batch,
                interpret=interpret), mesh,
        lambda batch: (P(None, None, batch), P(batch, None)), batch_axes)(slabs)


def dot_interaction_fused(
    stacked: jnp.ndarray,
    batch_axes: Sequence[str] = BATCH_AXES,
    block_batch: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """:func:`interaction_fused` for a ``[B, F, D]`` operand."""
    return interaction_fused(
        jnp.transpose(stacked, (1, 2, 0)), batch_axes, block_batch, interpret)
