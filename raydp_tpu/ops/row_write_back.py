"""Row write-back: put a batch's updated rows into embedding tables in place,
by block DMAs the kernel owns.

XLA:TPU keeps a ``float32[V, 16]`` table as ``{0,1:T(8,128)}``: transposed,
128 rows along the lanes and the 16 columns along sublanes, so that a table
takes its own bytes and not eight times that. Its scatter into that layout
writes one slot after another, padding slots included, and for tables of
about 15,000-300,000 rows it copies the whole table to the row-major layout
(16 padded to 128 lanes), scatters, and copies it back (its gather makes the
same copy to read rows: ``ops/row_gather.py`` is this kernel's mirror).

``table.T`` is a bitcast of that layout, and this kernel works on it: the
``[D, V]`` view stays in HBM, aliased to the result. For every 128-row block
that holds an updated row it reads the ``[D, 128]`` block into VMEM, puts the
block's rows in place (a lane rotate and a masked store per row) and writes
the block back. The ids come sorted and distinct, so a block's rows are
adjacent: a block is read once and written once, no two transfers in flight
name the same block, and the loop runs over the distinct ids alone. Reads are
issued ``ahead`` ids before their block is needed, into a ring of VMEM slots.
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

LANES = 128
# ids the reads run ahead of the writes, and VMEM slots per table (a power of
# two): a slot is taken again only after ring - ahead later blocks, so its
# write has had that long to land. 8 / 16 to 64 / 128 take the same time
# within 3 %: the loop is bound by the issue of its transfers (25-35 ns each,
# four an id for a parameter and its state) and by the 84 ns a row's rotate
# and masked store take, which overlap, not by the transfers' latency (chip
# runs, PERF.md Findings, PR 28)
AHEAD = 16
RING = 32
# what an id is to its 128-row block, as the kernel reads it
_OPENS, _CLOSES = 1, 2


def supports(shape, dtype) -> str:
    """Why a ``[V, D]`` leaf of this shape and dtype cannot go through the
    kernel; empty if it can."""
    if len(shape) != 2:
        return f"a leaf of {len(shape)} axes"
    if jnp.dtype(dtype) != jnp.float32:
        return f"a {jnp.dtype(dtype).name} leaf"
    if shape[1] % 8 or not 0 < shape[1] <= LANES:
        return f"a row of {shape[1]} (not a multiple of 8 up to {LANES})"
    return ""


def _kernel(idx_ref, edge_ref, count_ref, *refs, tables, ahead, ring):
    cols = refs[:tables]
    tabs = refs[2 * tables:3 * tables]  # the results: the tables themselves
    bufs, sem = refs[3 * tables:]
    n = count_ref[0]
    lanes = lax.broadcasted_iota(jnp.int32, bufs.shape[2:], 1)

    def block(j):
        return pl.ds(pl.multiple_of((idx_ref[j] >> 7) * LANES, LANES), LANES)

    def read(slot, where):
        return [pltpu.make_async_copy(
            tabs[k].at[:, where], bufs.at[k, slot], sem.at[k, slot])
            for k in range(tables)]

    def write(slot, where):
        return [pltpu.make_async_copy(
            bufs.at[k, slot], tabs[k].at[:, where], sem.at[k, slot])
            for k in range(tables)]

    def one(t, carry):
        """Start the read of the block that id ``t`` opens, if it opens one,
        and put id ``t - ahead`` into its block, by then read."""
        issued, done = carry
        opens = edge_ref[t] & _OPENS

        @pl.when(opens != 0)
        def _():
            slot, where = issued & (ring - 1), block(t)

            @pl.when(issued >= ring)
            def _():  # the slot's last block has to have left it
                for copy in write(slot, where):
                    copy.wait()

            for copy in read(slot, where):
                copy.start()

        # one loop for both, so that the kernel is traced and lowered once:
        # the first ``ahead`` rounds only read ahead (no edge, no lane)
        j, late = jnp.maximum(t - ahead, 0), t >= ahead
        edge = jnp.where(late, edge_ref[j], 0)
        slot, where = done & (ring - 1), block(j)

        @pl.when((edge & _OPENS) != 0)
        def _():
            for copy in read(slot, where):
                copy.wait()

        # row j of every table's rows into its lane of the block: rotated
        # there from its lane in the rows, stored under that lane's mask
        lane = jnp.where(late, idx_ref[j] & (LANES - 1), -1)
        base = pl.multiple_of((j >> 7) << 7, LANES)
        for k in range(tables):
            moved = pltpu.roll(
                cols[k][:, pl.ds(base, LANES)], (lane - j) & (LANES - 1), 1)
            pltpu.store(bufs.at[k, slot], moved, mask=lanes == lane)

        @pl.when((edge & _CLOSES) != 0)
        def _():
            for copy in write(slot, where):
                copy.start()

        return issued + opens, done + (edge >> 1)

    def drain(slot, carry):
        for copy in write(slot, block(0)):
            copy.wait()
        return carry

    _, done = lax.fori_loop(
        0, n + ahead, one, (jnp.int32(0), jnp.int32(0)))
    lax.fori_loop(0, jnp.minimum(done, ring), drain, 0)


def row_write_back(
    tables: Sequence[jax.Array],
    rows: Sequence[jax.Array],
    idx: jax.Array,
    *,
    interpret: bool | None = None,
    ahead: int = AHEAD,
    ring: int = RING,
):
    """``[t.at[idx].set(r, mode="drop") for t, r in zip(tables, rows)]``, bit
    for bit, for float32 ``[V, D]`` tables of one shape (a parameter and the
    optimizer state that follows it) and ``[N, D]`` rows. ``idx`` (int32
    ``[N]``) is ascending and without repeats below ``V``, the padding from
    ``V`` up: what ``row_update.sorted_unique`` gives."""
    # jitted, so that a process traces the kernel once for a table's shape
    # and not once in every program that holds it (a fit has two to four):
    # tracing and lowering eight kernels is 0.7-1.1 s a program on the chip's
    # host, and no compile cache holds a trace
    return _write_back(tuple(tables), tuple(rows), idx, ahead=ahead, ring=ring,
                       interpret=pallas_interpret(interpret))


@partial(jax.jit, static_argnames=("interpret", "ahead", "ring"))
def _write_back(tables, rows, idx, *, interpret, ahead, ring):
    (size, width), (slots,) = tables[0].shape, idx.shape
    why = supports(tables[0].shape, tables[0].dtype)
    if why:
        raise ValueError(f"row_write_back does not take {why}")
    if any(t.shape != (size, width) or t.dtype != jnp.float32 for t in tables):
        raise ValueError(f"tables of one shape and dtype, got {tables}")
    if len(rows) != len(tables) or any(
            r.shape != (slots, width) or r.dtype != jnp.float32 for r in rows):
        raise ValueError(
            f"float32 rows of [{slots}, {width}], one a table, got {rows}")
    if not ahead < ring or ring & (ring - 1):
        raise ValueError(f"ring {ring} has to be a power of two over {ahead}")
    # the kernel's loop is the scalar core's: what it would work out for
    # every id (does the id open a block, does it close one) is worked out
    # here for all of them at once, and both arrays run ``ahead`` past the
    # ids (no edge there), so that the look-ahead needs no bound
    idx = jnp.pad(idx.astype(jnp.int32), (0, ahead), constant_values=size)
    live, block = idx < size, idx >> 7
    differs = block[1:] != block[:-1]
    edge = live * (
        _OPENS * jnp.concatenate([jnp.ones(1, bool), differs])
        + _CLOSES * jnp.concatenate([differs | ~live[1:], jnp.ones(1, bool)]))
    count = jnp.sum(live, dtype=jnp.int32)[None]
    pad = -slots % LANES  # put() reads the rows by whole lane tiles
    cols = [jnp.pad(r.T, ((0, 0), (0, pad))) for r in rows]
    views = [t.T for t in tables]
    # The last block of a table whose rows do not fill it: on the device the
    # tiles of the layout are whole, so the block is there and its spare
    # lanes go out and come back as they were. The interpreter has no tiles:
    # there the view is padded to whole blocks first (a copy of the table,
    # which only a test pays).
    spare = -size % LANES if interpret else 0
    if spare:
        views = [jnp.pad(v, ((0, 0), (0, spare))) for v in views]
    k = len(tables)
    out = pl.pallas_call(
        partial(_kernel, tables=k, ahead=ahead, ring=ring),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)] * k
            + [pl.BlockSpec(memory_space=pltpu.HBM)] * k,
            out_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * k,
            scratch_shapes=[
                pltpu.VMEM((k, ring, width, LANES), jnp.float32),
                # one a slot: a slot's read is waited for before its write
                # starts, and its write before its next read (the chip has
                # 512 of them)
                pltpu.SemaphoreType.DMA((k, ring)),
            ],
        ),
        # in HBM by name: left to XLA, a table of under 300,000 rows is
        # copied to VMEM for the call and back after it
        out_shape=[pltpu.HBM(views[0].shape, jnp.float32)] * k,
        # operands: idx, edge, count, k rows, k tables
        input_output_aliases={3 + k + i: i for i in range(k)},
        name="row_write_back",
        interpret=interpret,
    )(idx, edge, count, *cols, *views)
    return [o[:, :size].T for o in out]
