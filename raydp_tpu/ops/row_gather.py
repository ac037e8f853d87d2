"""Row gather: read a batch's rows out of embedding tables by block DMAs the
kernel owns, the mirror of ``ops/row_write_back.py``.

XLA:TPU keeps a ``float32[V, 16]`` table as ``{0,1:T(8,128)}`` (transposed,
128 rows along the lanes). Its gather reads such a table where it lies only
from some two million rows on: a table of up to 299,000 rows it first copies
WHOLE to the row-major layout (16 columns padded to 128 lanes: eight times
the table's bytes, into VMEM where they fit and into HBM where not), one of
300,000 to 1.8 million rows it first copies whole into VMEM, every step.

``table.T`` is a bitcast of that layout, and this kernel reads it where it
lies: the ``[D, V]`` view stays in HBM and nothing is written to it. For every
128-row block that holds a wanted row it reads the ``[D, 128]`` block into a
ring slot in VMEM, rotates each wanted row from its lane of the block to its
slot's lane and selects it there, into a ``[D, N]`` result that lives in
VMEM. The ids come sorted and distinct, so a block's rows are adjacent: a
block is read once, and the loop runs over the distinct ids alone. The ids
go by chunks: a chunk's reads are issued ``ahead`` ids before its rows are
taken, and its rows are taken by straight-line code with no conditional
between two rows (the rotates of a chunk overlap) and one store a chunk.
"""

from __future__ import annotations

from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from raydp_tpu.ops.backend import pallas_interpret
from raydp_tpu.ops.row_write_back import AHEAD, LANES, RING, supports

__all__ = ["row_gather", "supports"]

# ids a chunk: as many rows are rotated and selected into their tile of the
# result by straight-line code between two rounds of transfers. A two-leaf
# call at the DLRM cells' ids (2048 slots; 1,056 / 1,132 / 1,303 distinct in
# 567 / 867 / 1,245 blocks of the tables of 93,145 / 286,181 / 10.1 M rows)
# takes 66 / 71 / 80 us; with a conditional an id before every row (the
# write-back's loop, read for written) it took 100 / 107 / 124, and XLA's
# gather takes 82 / 558 / 100. Chunks of 32 with a ring of 64: the same within
# 1 us. With 2 / 4 / 16 ids written out a round of the transfers' loop (Mosaic
# unrolls a loop whole or not at all) 61 / 59 / 57 us at the first of those
# tables, and 557 / 709 / 1,717 traced operations for this form's 481:
# tracing and lowering is what a table shape costs a process at set-up, about
# 0.1 s a shape and 0.15 s a shape and program; with all 16 ids written out
# in three loops (2,392 operations) eight shapes added 22 s to a 50-s set-up
# (chip runs, PERF.md Findings, PR 46)
CHUNK = 16


def _kernel(start_ref, slot_ref, shift_ref, lane_ref, count_ref, *refs,
            tables, chunk, ahead):
    tabs, outs = refs[:tables], refs[tables:2 * tables]
    bufs, sem = refs[2 * tables:]
    lanes = lax.broadcasted_iota(jnp.int32, bufs.shape[2:], 1)
    for out in outs:  # what a padding slot holds
        out[...] = jnp.zeros(out.shape, out.dtype)

    def transfer(j, wait):
        """Start, or wait for, the read of the block that id ``j`` opens, if
        it opens one."""
        start = start_ref[j]

        @pl.when(start >= 0)
        def _():
            slot = slot_ref[j]
            # a wait goes by the transfer's size, not by its source
            where = pl.ds(0 if wait else pl.multiple_of(start, LANES), LANES)
            for k in range(tables):
                copy = pltpu.make_async_copy(
                    tabs[k].at[:, where], bufs.at[k, slot], sem.at[k, slot])
                copy.wait() if wait else copy.start()

    def over_chunk(one):
        """``one(u)`` for the ids of a chunk: a rolled loop (a conditional an
        id, which nothing could overlap, and few operations to trace)."""
        def some(u, carry):
            one(u)
            return carry

        lax.fori_loop(0, chunk, some, 0)

    for first in range(0, ahead, chunk):
        over_chunk(lambda u: transfer(first + u, False))

    def one(c, carry):
        """Start the reads of the chunk ``ahead`` ids on, and take chunk
        ``c``'s rows out of their blocks, by then read."""
        first = c * chunk

        def ahead_and_here(u):  # one loop for both: half the loop's rounds
            transfer(first + ahead + u, False)
            transfer(first + u, True)

        over_chunk(ahead_and_here)
        # row idx[j] of every table into slot j of its rows: rotated there
        # from its lane of the block and selected under slot j's lane.
        # Straight-line code, so that the rotates of a chunk overlap; a
        # chunk's slots lie in one tile of the result: one store
        where = pl.ds(pl.multiple_of((first >> 7) << 7, LANES), LANES)
        tiles = [out[:, where] for out in outs]
        for j in (first + u for u in range(chunk)):
            slot, shift, here = slot_ref[j], shift_ref[j], lanes == lane_ref[j]
            for k in range(tables):
                tiles[k] = jnp.where(
                    here, pltpu.roll(bufs[k, slot], shift, 1), tiles[k])
        for out, tile in zip(outs, tiles):
            out[:, where] = tile
        return carry

    lax.fori_loop(0, (count_ref[0] + chunk - 1) // chunk, one, 0)


def row_gather(
    tables: Sequence[jax.Array],
    idx: jax.Array,
    *,
    interpret: bool | None = None,
    chunk: int = CHUNK,
    ahead: int = AHEAD,
    ring: int = RING,
):
    """``[t.at[idx].get(mode="clip") for t in tables]`` at every slot whose
    id is a row, bit for bit, for float32 ``[V, D]`` tables of one shape (a
    parameter and the optimizer state that follows it: one pass over the
    ids). ``idx`` (int32 ``[N]``) is ascending and without repeats below
    ``V``, the padding from ``V`` up: what ``row_update.sorted_unique``
    gives. A padding slot holds 0.0 (``clip`` would read the last row there;
    the row path never reads such a slot, and its write-back drops it)."""
    # jitted by the tables' shape, as the write-back is: a process traces the
    # kernel once a shape and not once in every program that holds it
    return _gather(tuple(tables), idx, chunk=chunk, ahead=ahead, ring=ring,
                   interpret=pallas_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret", "chunk", "ahead", "ring"))
def _gather(tables, idx, *, interpret, chunk, ahead, ring):
    (size, width), (slots,) = tables[0].shape, idx.shape
    why = supports(tables[0].shape, tables[0].dtype)
    if why:
        raise ValueError(f"row_gather does not take {why}")
    if any(t.shape != (size, width) or t.dtype != jnp.float32 for t in tables):
        raise ValueError(f"tables of one shape and dtype, got {tables}")
    if LANES % chunk or ahead % chunk or ring & (ring - 1) or (
            ahead + chunk > ring):
        # the blocks of the chunk whose rows are taken and of those read
        # ahead, one an id at most, have a slot each
        raise ValueError(
            f"chunks of {chunk} ids read {ahead} ids ahead need a ring (a "
            f"power of two) of {ahead + chunk} slots or more, not {ring}; a "
            f"chunk divides {LANES} and the ids read ahead")
    # the kernel's loop is the scalar core's: what it would work out for
    # every id is worked out here for all of them at once (the block it
    # opens, if it opens one; the ring slot that holds its block; how far its
    # row is rotated, to which lane); the arrays run ``ahead`` ids past the
    # last whole chunk (no block opens there, no lane is taken), so that the
    # look-ahead needs no bound. Few operations in the kernel: tracing it is
    # what a new table shape costs a process at set-up
    idx = jnp.pad(idx.astype(jnp.int32),
                  (0, -slots % chunk + ahead), constant_values=size)
    at = jnp.arange(idx.shape[0], dtype=jnp.int32)
    live, block = idx < size, idx >> 7
    opens = live & jnp.concatenate(
        [jnp.ones(1, bool), block[1:] != block[:-1]])
    start = jnp.where(opens, block << 7, -1)
    slot = (jnp.cumsum(opens, dtype=jnp.int32) - 1) & (ring - 1)
    shift = (at - idx) & (LANES - 1)
    lane = jnp.where(live, at & (LANES - 1), -1)
    count = jnp.sum(live, dtype=jnp.int32)[None]
    views = [t.T for t in tables]
    if interpret:
        # the last block of a table whose rows do not fill it is whole on
        # the device (the layout's tiles are) and its spare lanes are never
        # taken; the interpreter has no tiles, so there the view is padded (a
        # copy of the table, which only a test pays): as in the write-back
        views = [jnp.pad(v, ((0, 0), (0, -size % LANES))) for v in views]
    else:
        # in HBM by name, to XLA too: left to it, a table of under two
        # million rows is copied to VMEM whole for the call (no result
        # aliases the table here and holds it in HBM, as the write-back's)
        views = [pltpu.with_memory_space_constraint(v, pltpu.HBM)
                 for v in views]
    k = len(tables)
    out = pl.pallas_call(
        partial(_kernel, tables=k, chunk=chunk, ahead=ahead),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * k,
            out_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * k,
            scratch_shapes=[
                pltpu.VMEM((k, ring, width, LANES), jnp.float32),
                pltpu.SemaphoreType.DMA((k, ring)),
            ],
        ),
        # the rows by whole lane tiles: slot j is lane j % 128 of tile j // 128
        out_shape=[jax.ShapeDtypeStruct(
            (width, slots + -slots % LANES), jnp.float32)] * k,
        name="row_gather",
        interpret=interpret,
    )(start, slot, shift, lane, count, *views)
    return [o[:, :slots].T for o in out]
