"""Flash attention as a pallas TPU kernel.

Blockwise attention with online-softmax accumulators held in VMEM scratch:
the grid iterates (batch·head, q-block, k-block) with the k-block axis
innermost, so the per-q-block statistics (running max m, denominator l,
unnormalized output o) persist across k iterations and the full [T, T] score
matrix never materializes — O(T) memory instead of O(T²). Scores run on the
MXU (`preferred_element_type=f32`); masking and the softmax update run on the
VPU. Causal masking uses global positions (runtime offsets from SMEM); a
tile wholly in the future is never computed, and what it still costs
depends on the surface:
- causal self-attention from position 0 (``flash_attention`` with no
  window, forward and backward: static offsets 0, Tq = Tk) steps over the
  LIVE tiles only: the grid is flattened to (batch·head, tiles under the
  diagonal), 136 steps a head where the 16 x 16 rectangle has 256, and
  scalar-prefetched tables say which blocks a step reads
  (``causal_grid``, ``causal_steps``), so a dead tile costs no grid step
  and no DMA;
- a causal WINDOW (``flash_attention(..., window=W)``: a query sees its own
  position and the W - 1 before it) bounds the inner axis of a rectangular
  grid: it has only the steps a block's window can touch and the index
  maps start at the block's first live partner, so the blocks a window
  hides are neither stepped over nor fetched (``window_steps``), and the
  few dead tiles at a window's two edges are stepped over, fetched and
  predicated off; those calls carry names of their own
  (``flash_attention_window_fwd`` / ``_bwd_dq_dkv``, and ``_bwd_dq`` /
  ``_bwd_dkv`` where the two-call pass runs);
- with RUNTIME offsets (``flash_attention_stats`` and
  ``flash_backward_blocks`` under a ring) or Tq != Tk the grid is the full
  rectangle: a traced offset cannot shape a grid, so a dead tile's step is
  taken and its blocks are fetched, and only its arithmetic is predicated
  off (``_causal_block_live``);
- ``flash_decode`` steps over the whole cache and predicates off the
  blocks past a sequence's length.

One kernel family serves three surfaces:
- ``flash_attention``: normalized output, offsets 0 — the single-device /
  per-shard attention op. Its custom VJP is a blockwise FlashAttention-2
  backward over the saved output + logsumexp, so TRAINING is O(T) memory
  too — no [T, T] matrix in either direction. The backward pass of causal
  self-attention is ONE pallas call (``flash_attention_bwd_dq_dkv``): the
  dk/dv grid with the head's dq held in float32 in VMEM, every live tile's
  scores, probabilities and ``ds`` computed once, five products a tile. What
  that form does not cover (``backward_form``: runtime offsets, Tq != Tk,
  non-causal, unequal blocks, a dq past the VMEM a call may ask for) runs
  the two-call pass (``flash_attention_bwd_dq`` then ``_bwd_dkv``), which
  recomputes the tile in each call: seven products. Same bits either way.
- ``flash_attention_stats``: UNNORMALIZED output + (m, l) stats with caller
  offsets — the per-ring-step block product `parallel.ring_attention`
  merges across devices (``use_flash=True``).
- ``flash_decode``: incremental-decode attention of a few new query rows
  against a KV cache with per-sequence valid lengths (SMEM), sharing the
  same online-softmax update — so a decode step repeats the prefill's
  per-row arithmetic: bit-identical compiled on a TPU v5e, within 3e-7
  interpreted on XLA:CPU, whose matmul depends on the q-tile's row count
  (docs/serving.md). Optional int8 K/V with on-the-fly per-row dequant.

Two forward kernel bodies implement the same math: ``_flash_kernel`` (the
r05 two-term update — reference) and ``_flash_kernel_onepass`` (default),
which folds the per-block rescale of the [BQ, D] accumulator out of the VPU
hot loop by predicating it on the running max actually moving. When the max
is stable (the common case once a few blocks have been seen) the rescale is
skipped outright; when it fires, the skipped-row multiplies are ×exp(0)=1,
so the two kernels are bit-identical by construction — the parity gate in
the bench is exact equality, not allclose.

Off-TPU the same kernels run in interpret mode, so CPU-mesh tests exercise
the identical code path.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from raydp_tpu.ops.backend import VMEM_ASK_BOUND_BYTES, pallas_interpret

NEG_INF = -1e30


def use_onepass_default() -> bool:
    """Whether the one-pass (deferred-rescale) forward kernel is the default.
    Env escape hatch ``RAYDP_TPU_FLASH_ONEPASS=0`` pins the reference kernel
    (bisecting a numerics report; the two are bit-identical by design)."""
    return os.environ.get("RAYDP_TPU_FLASH_ONEPASS", "1").lower() not in (
        "0", "false", "off"
    )


def _causal_block_live(q_off_ref, k_off_ref, qi, ki, block_q, block_k, causal,
                       window=None):
    """Whether a (q-block, k-block) pair has any unmasked entry. Causal: a
    k-block entirely in the future contributes nothing — skip its matmul +
    update outright (on a rectangular grid its step and its fetch remain;
    the live grid has no such step, so there this is always true).
    Offsets are runtime values
    (SMEM), so the predicate is computed at runtime too. ``window``: a
    k-block entirely past the window (every key ``window`` or more
    positions behind the block's first query) is dead as well."""
    if not causal:
        return ki >= 0
    q_last = q_off_ref[0] + qi * block_q + block_q - 1
    k_first = k_off_ref[0] + ki * block_k
    live = q_last >= k_first
    if window is not None:
        q_first = q_off_ref[0] + qi * block_q
        live = jnp.logical_and(live, k_first + block_k - 1 > q_first - window)
    return live


def _causal_mask(s, q_off_ref, k_off_ref, qi, ki, block_q, block_k,
                 window=None):
    """Mask scores s [BQ, BK] to NEG_INF where global k position > q
    position, and with a ``window`` where the key lies ``window`` or more
    positions behind the query (a query sees its own position and the
    ``window - 1`` before it). Shared by the forward and the backward
    kernels so the mask semantics can never diverge between them."""
    q_pos = q_off_ref[0] + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = k_off_ref[0] + ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    keep = q_pos >= k_pos
    if window is not None:
        keep = jnp.logical_and(keep, q_pos - k_pos < window)
    return jnp.where(keep, s, NEG_INF)


# -- a window bounds the GRID ---------------------------------------------------
# With ``window`` the inner grid axis has only as many steps as a block's
# window can touch, and step j of q-block i reads k-block ``first + j``
# (dk/dv: step j of k-block i reads q-block ``first + j``): the blocks past
# the window are neither stepped over nor fetched. ``first`` is computed the
# same way in the index maps (which clamp it into the array: a block index
# must exist) and in the kernels (which do not, and predicate a step past
# the last block off: clamped, it would read a block already seen).


def _first_k_block(qi, block_q, block_k, window):
    """The first k-block a q-block's window touches."""
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def _first_q_block(kj, block_q, block_k):
    """The first q-block that sees a k-block (causal: the one its first key
    lies in)."""
    return (kj * block_k) // block_q


def window_steps(t: int, block_q: int, block_k: int, window: int) -> tuple:
    """(k-steps of the forward and dq grids, q-steps of the dk/dv grid) for
    a causal window over ``t`` positions: the most blocks any q-block's
    window touches (its keys span ``window + block_q - 1`` positions: never
    more than ``ceil((window + block_q - 1) / block_k) + 1`` blocks), and
    the most q-blocks that see any k-block."""
    nq, nk = t // block_q, t // block_k
    k_steps = max(
        ((i + 1) * block_q - 1) // block_k
        - max(i * block_q - (window - 1), 0) // block_k + 1
        for i in range(nq))
    q_steps = max(
        min(((j + 1) * block_k - 1 + window - 1) // block_q, nq - 1)
        - (j * block_k) // block_q + 1
        for j in range(nk))
    return k_steps, q_steps


# -- a causal call steps over its LIVE tiles only -----------------------------
# Causal self-attention from position 0 (no window, both offsets the static
# 0, Tq = Tk) knows at trace time which tiles lie under the diagonal, so its
# grid is FLATTENED to (batch·head, live tiles): step -> (outer block, inner
# block) tables, built with numpy and scalar-prefetched into SMEM, drive the
# index maps, and a tile beyond the diagonal costs no grid step and no DMA.
# The kernel bodies are the rectangular grid's (``_tile_of_step`` hands them
# their place either way), so are the tiles and their order: the same bits.

# the tables' most steps: two int32 a step, so half of a v5e's 1 MiB of SMEM
# at most (compiled for a described one: 65,341 steps go through, 131,328
# run out of SMEM). A row of 362 blocks or more keeps the rectangular grid.
LIVE_GRID_MAX_STEPS = 1 << 16


def causal_steps(t: int, block_q: int, block_k: int) -> tuple:
    """(steps a head of the RECTANGULAR grid over ``t`` causal positions,
    the tiles of it with an unmasked entry: the steps of the live grid).
    136 of 256 at 16 x 16 equal blocks."""
    nq, nk = t // block_q, t // block_k
    return nq * nk, sum(
        ((i + 1) * block_q - 1) // block_k + 1 for i in range(nq))


def causal_grid(t: int, tk: int, block_q: int, block_k: int, *,
                causal: bool = True, window: int | None = None,
                q_offset=0, k_offset=0) -> str:
    """Which grid the calls of these shapes and arguments take: ``"live"``
    (flattened over the tiles under the diagonal), ``"window"`` (bounded by
    the window: ``window_steps``) or ``"rectangular"`` with why, after a
    colon: a traced offset cannot shape a grid, ``Tq != Tk`` and a
    non-causal call are not built, tables past ``LIVE_GRID_MAX_STEPS`` do
    not fit. Decided from what the call sees, by no flag."""
    if not causal:
        return "rectangular:not causal"
    if not all(isinstance(o, int) and o == 0 for o in (q_offset, k_offset)):
        return "rectangular:runtime offsets"
    if t != tk:
        return "rectangular:queries and keys differ in length"
    if window is not None and window < tk:
        return "window"
    if causal_steps(t, block_q, block_k)[1] > LIVE_GRID_MAX_STEPS:
        return "rectangular:more live tiles than the tables hold"
    return "live"


def _live_tiles(t: int, block_q: int, block_k: int, outer: str):
    """int32 [2, live tiles]: the (outer block, inner block) of every step
    of the flattened grid, an outer block's inner ones ascending. ``outer``
    ``"q"``: the forward's and the dq call's order (a q-block, then its
    k-blocks up to the diagonal's); ``"k"``: the dk/dv and the fused call's
    (a k-block, then the q-blocks from ``_first_q_block`` on)."""
    import numpy as np

    nq, nk = t // block_q, t // block_k
    if outer == "q":
        pairs = [(i, j) for i in range(nq)
                 for j in range(((i + 1) * block_q - 1) // block_k + 1)]
    else:
        pairs = [(j, i) for j in range(nk)
                 for i in range(_first_q_block(j, block_q, block_k), nq)]
    return np.asarray(pairs, np.int32).T


def _on_live_tiles(kernel):
    """``kernel`` on the flattened grid: the two tables come first (scalar
    prefetch), and the kernel is told its tile and whether the step is its
    outer block's first and last (the neighbouring steps' outer blocks
    differ) in place of reading them off a rectangular grid."""
    from jax.experimental import pallas as pl

    def body(outer_ref, inner_ref, *refs):
        step, steps = pl.program_id(1), pl.num_programs(1)
        outer = outer_ref[step]
        first = jnp.logical_or(
            step == 0, outer_ref[jnp.maximum(step - 1, 0)] != outer)

        def last():
            return jnp.logical_or(
                step == steps - 1,
                outer_ref[jnp.minimum(step + 1, steps - 1)] != outer)

        kernel(*refs, tile=(outer, inner_ref[step], first, last))

    return body


def _tile_of_step(tile, first_inner=None):
    """(outer block, inner block, whether this is the outer block's first
    step, a function that says whether it is its last) of a grid step:
    ``tile`` as ``_on_live_tiles`` gives it, or read off the rectangular
    grid, whose inner axis starts at block ``first_inner(outer)`` where a
    window bounds it. The last is a function so that a rectangular call
    lowers to the operations it always had, in their order."""
    from jax.experimental import pallas as pl

    if tile is not None:
        return tile
    outer, step = pl.program_id(1), pl.program_id(2)
    inner = step if first_inner is None else step + first_inner(outer)
    return outer, inner, step == 0, lambda: step == pl.num_programs(2) - 1


def _grid_plan(kernel, live, outer, bh, t, block_q, block_k, steps,
               inner_block):
    """A call's (kernel, grid, scalar-prefetched tables, index map of a
    block that follows the grid's OUTER blocks, index map of one that
    follows its inner blocks). ``outer``: ``"q"`` (a q-block, then its
    k-blocks: the forward and the dq call) or ``"k"`` (a k-block, then its
    q-blocks: the dk/dv and the fused call). ``live``: the flattened grid
    over the tiles under the diagonal, both blocks of a step read from the
    tables; else the rectangle (bh, outer blocks, inner steps) of ``steps``,
    step j of outer block o at inner block ``inner_block(o, j)``."""
    if live:
        tables = _live_tiles(t, block_q, block_k, outer)

        def outer_at(b_, s, outers, inners):
            return (b_, outers[s], 0)

        def inner_at(b_, s, outers, inners):
            return (b_, inners[s], 0)

        return (_on_live_tiles(kernel), (bh, tables.shape[1]), tuple(tables),
                outer_at, inner_at)

    def outer_at(b_, o, j):
        return (b_, o, 0)

    def inner_at(b_, o, j):
        return (b_, inner_block(o, j), 0)

    return kernel, (bh, *steps), (), outer_at, inner_at


def _flash_kernel(
    q_off_ref, k_off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
    o_acc, m_acc, l_acc, *, scale, causal, block_q, block_k, normalize,
    window=None, inner_blocks=None, tile=None,
):
    from jax.experimental import pallas as pl

    qi, ki, first, last = _tile_of_step(tile, window and functools.partial(
        _first_k_block, block_q=block_q, block_k=block_k, window=window))

    @pl.when(first)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, NEG_INF)
        l_acc[:] = jnp.zeros_like(l_acc)

    block_live = _causal_block_live(
        q_off_ref, k_off_ref, qi, ki, block_q, block_k, causal, window
    )
    if window is not None:
        block_live = jnp.logical_and(block_live, ki < inner_blocks)

    @pl.when(block_live)
    def _accumulate():
        q = q_ref[0]  # [BQ, D]
        k = k_ref[0]  # [BK, D]
        v = v_ref[0]  # [BK, D]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]

        if causal:
            scores = _causal_mask(
                scores, q_off_ref, k_off_ref, qi, ki, block_q, block_k,
                window,
            )

        m_prev = m_acc[:, :1]  # [BQ, 1] (stats broadcast across lanes)
        l_prev = l_acc[:, :1]
        block_max = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        p = jnp.exp(scores - m_new)  # rows that are all -inf give p == 0
        if causal:
            p = jnp.where(scores > NEG_INF / 2, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        o_new = alpha * o_acc[:] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

        o_acc[:] = o_new
        m_acc[:] = jnp.broadcast_to(m_new, m_acc.shape)
        l_acc[:] = jnp.broadcast_to(l_new, l_acc.shape)

    @pl.when(last())
    def _finalize():
        if normalize:
            o_ref[0] = (
                o_acc[:] / jnp.maximum(l_acc[:, :1], 1e-30)
            ).astype(o_ref.dtype)
        else:
            o_ref[0] = o_acc[:].astype(o_ref.dtype)
        m_ref[0] = m_acc[:, :1]
        l_ref[0] = l_acc[:, :1]


def _flash_kernel_onepass(
    q_off_ref, k_off_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
    o_acc, m_acc, l_acc, *, scale, causal, block_q, block_k, normalize,
    window=None, inner_blocks=None, tile=None,
):
    """One-pass online softmax with the accumulator rescale deferred.

    The r05 roofline blames the per-block ``alpha * o_acc`` rescale — a
    [BQ, D] VPU multiply every k iteration — for the LM attention VPU wall.
    Here the rescale (of both o and l) only runs when the running max
    actually moved (``any(block_max > m_prev)``); otherwise alpha == exp(0)
    == 1 exactly and the multiply is dead weight. Normalization stays
    deferred to the finalize step, so the hot loop is: one MXU score dot,
    one exp, one MXU p·v dot, one add. Bit-identical to ``_flash_kernel``
    (the gated multiplies are exactly ×1.0 when skipped)."""
    from jax.experimental import pallas as pl

    qi, ki, first, last = _tile_of_step(tile, window and functools.partial(
        _first_k_block, block_q=block_q, block_k=block_k, window=window))

    @pl.when(first)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, NEG_INF)
        l_acc[:] = jnp.zeros_like(l_acc)

    block_live = _causal_block_live(
        q_off_ref, k_off_ref, qi, ki, block_q, block_k, causal, window
    )
    if window is not None:
        block_live = jnp.logical_and(block_live, ki < inner_blocks)

    @pl.when(block_live)
    def _accumulate():
        q = q_ref[0]  # [BQ, D]
        k = k_ref[0]  # [BK, D]
        v = v_ref[0]  # [BK, D]
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [BQ, BK]

        if causal:
            scores = _causal_mask(
                scores, q_off_ref, k_off_ref, qi, ki, block_q, block_k,
                window,
            )

        m_prev = m_acc[:, :1]  # [BQ, 1]
        l_prev = l_acc[:, :1]
        block_max = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        p = jnp.exp(scores - m_new)
        if causal:
            p = jnp.where(scores > NEG_INF / 2, p, 0.0)

        p_sum = jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        moved = jnp.any(block_max > m_prev)

        # the rescale branch keeps the reference kernel's exact expression
        # shape (alpha·acc + new in one statement) so XLA's fusion decisions
        # — FMA contraction in particular — can't introduce 1-ulp drift; the
        # skip branch drops the ×1.0 multiplies outright (exact identity)
        @pl.when(moved)
        def _rescale():
            alpha = jnp.exp(m_prev - m_new)
            l_acc[:] = jnp.broadcast_to(alpha * l_prev + p_sum, l_acc.shape)
            o_acc[:] = alpha * o_acc[:] + pv

        @pl.when(jnp.logical_not(moved))
        def _no_rescale():
            l_acc[:] = jnp.broadcast_to(l_prev + p_sum, l_acc.shape)
            o_acc[:] = o_acc[:] + pv

        m_acc[:] = jnp.broadcast_to(m_new, m_acc.shape)

    @pl.when(last())
    def _finalize():
        if normalize:
            o_ref[0] = (
                o_acc[:] / jnp.maximum(l_acc[:, :1], 1e-30)
            ).astype(o_ref.dtype)
        else:
            o_ref[0] = o_acc[:].astype(o_ref.dtype)
        m_ref[0] = m_acc[:, :1]
        l_ref[0] = l_acc[:, :1]


def _union_vma(*arrays):
    """Union of the operands' varying manual axes (empty outside shard_map).
    Under shard_map — the only way Mosaic kernels run multi-device — a
    pallas_call's out_shape must carry it."""
    return frozenset().union(*(jax.typeof(a).vma for a in arrays))


def _vary_like(x, vma):
    """SMEM scalars must vary over the same axes as the kernel's operands;
    an offset derived from ``lax.axis_index`` already does."""
    missing = tuple(vma - jax.typeof(x).vma)
    return jax.lax.pcast(x, missing, to="varying") if missing else x


def _window_for(window, causal, t, tk, q_offset=0, k_offset=0):
    """``window`` as the kernels take it: None where it hides no key (a
    window of the whole sequence or more IS the causal call, the same
    program under the same name); refused where the kernels do not build
    it: the grids count a block's first live neighbour from position 0, so
    both offsets must be the static 0 (a ring's step is not)."""
    if window is None:
        return None
    if (not causal or t != tk or window < 1
            or not all(isinstance(o, int) and o == 0
                       for o in (q_offset, k_offset))):
        raise ValueError(
            f"a window ({window}) is built for causal self-attention only "
            f"(causal={causal}, {t} queries over {tk} keys, offsets "
            f"{q_offset!r} and {k_offset!r} that must be the static 0)")
    return None if window >= tk else int(window)


def _flash_call(
    q, k, v, q_offset, k_offset, causal, block_q, block_k, interpret,
    normalize, onepass=None, window=None,
):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = pallas_interpret(interpret)
    if onepass is None:
        onepass = use_onepass_default()
    b, h, t, d = q.shape
    tk, dv = k.shape[2], v.shape[3]  # values, and o, at a width of their own
    block_q, block_k = _blocks(t, tk, d, q.dtype.itemsize, block_q, block_k,
                               dv)
    bh = b * h
    qf = q.reshape(bh, t, d)
    kf = k.reshape(bh, tk, d)
    vf = v.reshape(bh, tk, dv)

    window = _window_for(window, causal, t, tk, q_offset, k_offset)
    live = causal_grid(t, tk, block_q, block_k, causal=causal, window=window,
                       q_offset=q_offset, k_offset=k_offset) == "live"
    k_steps, k_block = tk // block_k, (lambda i, j: j)
    if window is not None:
        # the grid follows the window: only the k-blocks it can touch are
        # stepped over and fetched (``window_steps``)
        k_steps, _ = window_steps(t, block_q, block_k, window)
        last = tk // block_k - 1

        def k_block(i, j):
            return jnp.minimum(
                _first_k_block(i, block_q, block_k, window) + j, last)

    kernel = functools.partial(
        _flash_kernel_onepass if onepass else _flash_kernel,
        scale=d**-0.5, causal=causal, block_q=block_q, block_k=block_k,
        normalize=normalize,
        **({} if window is None else dict(
            window=window, inner_blocks=tk // block_k)),
    )
    union = _union_vma(qf, kf, vf)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=union)

    q_off = _vary_like(jnp.asarray([q_offset], jnp.int32), union)
    k_off = _vary_like(jnp.asarray([k_offset], jnp.int32), union)

    kernel, grid, tables, q_at, k_at = _grid_plan(
        kernel, live, "q", bh, t, block_q, block_k,
        (t // block_q, k_steps), k_block)

    out_dtype = q.dtype if normalize else jnp.float32
    o, m, l = pl.pallas_call(  # noqa: E741
        kernel,
        out_shape=(
            sds((bh, t, dv), out_dtype),
            sds((bh, t, 1), jnp.float32),
            sds((bh, t, 1), jnp.float32),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=grid,
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec((1, block_q, d), q_at),
                pl.BlockSpec((1, block_k, d), k_at),
                pl.BlockSpec((1, block_k, dv), k_at),
            ],
            out_specs=(
                pl.BlockSpec((1, block_q, dv), q_at),
                pl.BlockSpec((1, block_q, 1), q_at),
                pl.BlockSpec((1, block_q, 1), q_at),
            ),
            scratch_shapes=[
                pltpu.VMEM((block_q, dv), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
            ],
        ),
        interpret=interpret,
        # a window call under a name of its own: a reader that counts every
        # ``flash_attention_fwd`` call as causal would credit it with the
        # pairs the window hides
        name="flash_attention_fwd" if window is None
        else "flash_attention_window_fwd",
    )(*(_vary_like(jnp.asarray(table), union) for table in tables),
      q_off, k_off, qf, kf, vf)
    return (
        o.reshape(b, h, t, dv),
        m.reshape(b, h, t),
        l.reshape(b, h, t),
    )


def flash_attention_stats(
    q, k, v, q_offset, k_offset, causal: bool = False,
    block_q: int | None = None, block_k: int | None = None,
    interpret: bool | None = None,
):
    """One blockwise-attention pass returning (o_unnormalized, m, l).

    Shapes: q [B,H,Tq,D], k/v [B,H,Tk,D]; offsets are scalars (traced OK)
    giving the blocks' global positions for causal masking. Outputs:
    o [B,H,Tq,D] (unnormalized, f32), m and l [B,H,Tq] — merge across passes
    with the standard flash merge, divide by l at the end.
    """
    return _flash_call(
        q, k, v, q_offset, k_offset, causal, block_q, block_k, interpret,
        normalize=False,
    )


def _flash_forward(
    q, k, v, causal: bool, block_q: int, block_k: int, interpret: bool | None,
    window: int | None = None,
):
    o, _, _ = _flash_call(
        q, k, v, 0, 0, causal, block_q, block_k, interpret, normalize=True,
        window=window,
    )
    return o


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2): blockwise dq/dk/dv from the saved
# normalized output and per-row logsumexp — O(T) memory for TRAINING too, not
# just the forward. TPU has no cross-block atomics, so a gradient lives in
# VMEM while its tiles arrive. Causal self-attention at equal blocks and
# static offsets runs ONE call (``_bwd_fused_kernel``): the dk/dv grid
# (k-block outer, q-block inner) with the whole head's dq held in a float32
# scratch, every live tile's scores, probabilities and ``ds`` computed once.
# Everything else (``backward_form``) runs the two-call pass: a dq call that
# iterates k-blocks innermost (one q-block's dq in VMEM) and a dk/dv call
# that iterates q-blocks innermost (one k-block's dk + dv), each of which
# recomputes the tile. All three kernels take the tile from ``_bwd_tile``.
# ---------------------------------------------------------------------------

# the default scoped VMEM of a Mosaic call on the chips this runs on; what
# the fused call may ask for through ``vmem_limit_bytes`` is
# ``VMEM_ASK_BOUND_BYTES`` (``ops/backend.py``)
VMEM_DEFAULT_BYTES = 16 * 2**20


def _bwd_tile(
    q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
    qi, ki, *, scale, causal, block_q, block_k, window,
):
    """One (q-block, k-block) tile of the backward pass: the operands and
    ``p`` [BQ, BK], ``ds`` [BQ, BK] in float32. The one place the mask and
    the ``ds`` expression are written, for the fused and the two-call
    kernels alike."""
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]  # [BQ, 1]
    dsum = dsum_ref[0]  # [BQ, 1]  rowsum(do * o)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale
    if causal:
        s = _causal_mask(s, q_off_ref, k_off_ref, qi, ki, block_q, block_k,
                         window)
    p = jnp.exp(s - lse)  # masked entries: exp(NEG_INF - lse) == 0
    dp = jax.lax.dot_general(
        do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    ds = p * (dp - dsum) * scale
    return q, k, do, p, ds


def _bwd_dq_kernel(
    q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, dsum_ref,
    dq_ref, dq_acc, *, scale, causal, block_q, block_k, window=None,
    inner_blocks=None, tile=None,
):
    from jax.experimental import pallas as pl

    qi, ki, first, last = _tile_of_step(tile, window and functools.partial(
        _first_k_block, block_q=block_q, block_k=block_k, window=window))

    @pl.when(first)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    block_live = _causal_block_live(
        q_off_ref, k_off_ref, qi, ki, block_q, block_k, causal, window
    )
    if window is not None:
        block_live = jnp.logical_and(block_live, ki < inner_blocks)

    @pl.when(block_live)
    def _accumulate():
        _, k, _, _, ds = _bwd_tile(
            q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
            dsum_ref, qi, ki, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window)
        dq_acc[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(last())
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _bwd_dkv_kernel(
    q_off_ref, k_off_ref, k_ref, v_ref, q_ref, do_ref, lse_ref, dsum_ref,
    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, block_q, block_k,
    window=None, inner_blocks=None, tile=None,
):
    from jax.experimental import pallas as pl

    kj, qi, first, last = _tile_of_step(tile, window and functools.partial(
        _first_q_block, block_q=block_q, block_k=block_k))

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    block_live = _causal_block_live(
        q_off_ref, k_off_ref, qi, kj, block_q, block_k, causal, window
    )
    if window is not None:
        block_live = jnp.logical_and(block_live, qi < inner_blocks)

    @pl.when(block_live)
    def _accumulate():
        q, _, do, p, ds = _bwd_tile(
            q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
            dsum_ref, qi, kj, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, window=window)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    @pl.when(last())
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_fused_kernel(
    q_off_ref, k_off_ref, k_ref, v_ref, q_ref, do_ref, lse_ref, dsum_ref,
    dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale, block,
    window=None, inner_blocks=None, tile=None,
):
    """The whole backward pass of causal self-attention in the dk/dv
    kernel's grid (k-block ``kj`` outer, q-block inner, equal blocks): a
    live tile is computed ONCE and feeds dv, dk (the k-block's accumulators)
    and dq (``dq_acc[qi]``: the head's dq, float32, in VMEM). A q-block
    takes its k-blocks in ascending order, as the dq kernel adds them: it is
    zeroed at its first (k-block 0, or the first its window touches) and
    complete at its diagonal tile, the first live step of ``kj == qi``,
    where it is written, in the operands' dtype, to the out block that
    follows the outer axis."""
    from jax.experimental import pallas as pl

    kj, qi, first, last = _tile_of_step(tile, window and functools.partial(
        _first_q_block, block_q=block, block_k=block))

    @pl.when(first)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    block_live = _causal_block_live(
        q_off_ref, k_off_ref, qi, kj, block, block, True, window
    )
    if window is not None:
        block_live = jnp.logical_and(block_live, qi < inner_blocks)

    @pl.when(block_live)
    def _accumulate():
        q, k, do, p, ds = _bwd_tile(
            q_off_ref, k_off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
            dsum_ref, qi, kj, scale=scale, causal=True, block_q=block,
            block_k=block, window=window)
        dv_acc[:] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        dk_acc[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

        first = 0 if window is None else _first_k_block(
            qi, block, block, window)

        @pl.when(kj == first)
        def _first():
            dq_acc[qi] = jnp.zeros(dq_acc.shape[1:], dq_acc.dtype)

        dq_acc[qi] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

        @pl.when(qi == kj)
        def _diagonal():
            dq_ref[0] = dq_acc[qi].astype(dq_ref.dtype)

    @pl.when(last())
    def _finalize():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(
    q, k, v, o, lse, g, causal, block_q, block_k, interpret, window=None
):
    """Blockwise dq/dk/dv for the single-device surface (offsets 0).
    lse: [B,H,T] logsumexp of the scaled scores; o: normalized forward
    output; g: cotangent of o."""
    dsum = jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )
    return flash_backward_blocks(
        q, k, v, lse, dsum, g, 0, 0, causal, block_q, block_k, interpret,
        window,
    )


def _lanes(head_dim: int) -> int:
    """A row of ``head_dim`` elements as VMEM holds it: whole 128-lane tiles."""
    return -(-head_dim // 128) * 128


def dq_resident_bytes(t: int, head_dim: int) -> int:
    """What the fused backward call keeps in VMEM for a head's dq: float32
    ``[t, head_dim]``, a head narrower than the 128 lanes padded to them."""
    return t * _lanes(head_dim) * 4


def fused_vmem_bytes(t: int, head_dim: int, block: int, itemsize: int,
                     value_dim: int | None = None) -> int:
    """The VMEM the fused backward call asks for, from its shapes: the
    head's dq; the dk and dv accumulators; two buffers of every pipelined
    block (q, k in and dq, dk out at ``head_dim``; do, v in and dv out at
    ``value_dim``, the values' width where it is not the keys'; and
    ``lse`` / ``dsum``, whose
    ``[block, 1]`` float32 columns are padded to the lanes); and the tile's
    ``[block, block]`` float32 values (s, p, dp, ds, the mask's two iotas
    and what the compiler keeps beside them), counted as EIGHT: compiled
    for a described v5e the call needed 2.5 of them beside the rest at bf16
    1024-row tiles (16.6 MB + dq) and 6.3 at float32 512-row ones (11.3 MB
    + dq), and what is asked for and not used costs nothing
    (``tests/test_tpu_compile_kernels.py`` compiles the cells' shapes).
    FLOAT32 operands add the three bfloat16 parts a product at ``highest``
    splits each operand tile into: at heads of 256 over 256 in 256-row
    tiles the call needs 16.65 MB where the rest of this count is 15.2,
    and the 16 MiB of ``VMEM_DEFAULT_BYTES`` covered it by 0.7 %. The ask
    matters beyond covering the need: XLA keeps an operand in VMEM across
    the call where the two fit its 96 MiB, and at 20 heads the row
    statistics' ``[20, 8192, 1]`` float32 (lane-padded: 80 MiB exactly)
    beside an ask of exactly 16 MiB passed that test and failed the
    allocation by 136 KB (my chip run, PR 53); an ask that says what the
    call needs lies off that edge."""
    row = block * _lanes(head_dim)
    row_v = block * _lanes(value_dim or head_dim)
    split = 3 * (4 * row + 3 * row_v) * 2 if itemsize >= 4 else 0
    return (dq_resident_bytes(t, head_dim) + (row + row_v) * 4
            + 2 * ((4 * row + 3 * row_v) * itemsize + 2 * block * 128 * 4)
            + 8 * block * block * 4 + split)


def _blocks(t, tk, head_dim, itemsize, block_q, block_k, value_dim=None):
    """The tiles of a call, forward and backward alike: the caller's, or
    ``pick_blocks``' for this head, value and operand width, clamped to the
    sequence lengths, which they must divide."""
    auto_q, auto_k = pick_blocks(t, tk, head_dim=head_dim, itemsize=itemsize,
                                 value_dim=value_dim)
    block_q, block_k = min(block_q or auto_q, t), min(block_k or auto_k, tk)
    if t % block_q or tk % block_k:
        raise ValueError(
            f"sequence lengths ({t}, {tk}) must divide blocks ({block_q}, {block_k})"
        )
    return block_q, block_k


def backward_form(
    t: int, tk: int, head_dim: int, itemsize: int = 2, *, causal: bool = True,
    block_q: int | None = None, block_k: int | None = None,
    q_offset=0, k_offset=0, value_dim: int | None = None,
) -> str:
    """Which form the backward pass of these shapes and arguments takes:
    ``"fused"`` (one call, every live tile once) for causal self-attention
    (``t == tk``) at equal blocks and the static offsets 0 whose float32 dq
    fits the VMEM a call may ask for; ``"two_call"`` for everything else: a
    ring step's runtime offsets, ``t != tk``, a non-causal call (its dq is
    complete at no tile of a k-outer grid but the last), unequal blocks.
    ``value_dim``: the width of v, o and do where it is not q's and k's.
    Decided from what the call sees, by no flag."""
    block_q, block_k = _blocks(t, tk, head_dim, itemsize, block_q, block_k,
                               value_dim)
    static = all(isinstance(o, int) and o == 0 for o in (q_offset, k_offset))
    if (causal and static and t == tk and block_q == block_k
            and fused_vmem_bytes(t, head_dim, block_q, itemsize, value_dim)
            <= VMEM_ASK_BOUND_BYTES):
        return "fused"
    return "two_call"


def flash_backward_blocks(
    q, k, v, lse, dsum, g, q_offset, k_offset, causal: bool = False,
    block_q: int | None = None, block_k: int | None = None,
    interpret: bool | None = None, window: int | None = None,
):
    """One blockwise-backward pass: (dq, dk, dv) partials of q [B,H,Tq,D]
    against k/v [B,H,Tk,D], given the GLOBAL per-row logsumexp ``lse`` and
    ``dsum = rowsum(do·o)`` [B,H,Tq] and the blocks' global positions for
    causal masking — the per-ring-step counterpart of
    ``flash_attention_stats``: `parallel.ring_attention` sums these partials
    as K/V (and their gradient accumulators) rotate around the ring.
    ``window`` (offsets 0, Tq = Tk: ``flash_attention``'s own backward pass):
    the grids follow it, as the forward's does. ``backward_form`` says
    whether the pass is the one fused call or the dq and the dk/dv call."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = pallas_interpret(interpret)
    b, h, t, d = q.shape
    tk, dv = k.shape[2], v.shape[3]  # v, do and dv at the values' own width
    block_q, block_k = _blocks(t, tk, d, q.dtype.itemsize, block_q, block_k,
                               dv)
    form = backward_form(
        t, tk, d, q.dtype.itemsize, causal=causal, block_q=block_q,
        block_k=block_k, q_offset=q_offset, k_offset=k_offset, value_dim=dv)
    bh = b * h
    scale = d**-0.5

    qf = q.reshape(bh, t, d)
    kf = k.reshape(bh, tk, d)
    vf = v.reshape(bh, tk, dv)
    dof = g.reshape(bh, t, dv)
    lsef = lse.reshape(bh, t, 1)
    dsumf = dsum.astype(jnp.float32).reshape(bh, t, 1)

    union = _union_vma(qf, kf, vf, dof)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, vma=union)

    q_off = _vary_like(jnp.asarray([q_offset], jnp.int32).reshape(1), union)
    k_off = _vary_like(jnp.asarray([k_offset], jnp.int32).reshape(1), union)

    window = _window_for(window, causal, t, tk, q_offset, k_offset)
    live = causal_grid(t, tk, block_q, block_k, causal=causal, window=window,
                       q_offset=q_offset, k_offset=k_offset) == "live"
    k_steps, q_steps = tk // block_k, t // block_q
    k_block = q_block = (lambda outer, j: j)
    name = "flash_attention_bwd_"
    dq_window = dkv_window = {}
    if window is not None:
        k_steps, q_steps = window_steps(t, block_q, block_k, window)
        last_k, last_q = tk // block_k - 1, t // block_q - 1
        name = "flash_attention_window_bwd_"
        dq_window = dict(window=window, inner_blocks=tk // block_k)
        dkv_window = dict(window=window, inner_blocks=t // block_q)

        def k_block(i, j):
            return jnp.minimum(
                _first_k_block(i, block_q, block_k, window) + j, last_k)

        def q_block(kj, j):
            return jnp.minimum(
                _first_q_block(kj, block_q, block_k) + j, last_q)

    def call(kernel, outer, operands, blocks, outs, scratch, **params):
        """One backward call on the grid its shapes take. ``outer``: the
        outer blocks' axis (``"q"``: q-block, then its k-blocks, the dq
        call; ``"k"``: k-block, then its q-blocks, the dk/dv and the fused
        call). ``blocks``: per operand (then per result in ``outs``) its
        block's (rows, width, whether it follows the OUTER axis)."""
        kernel, grid, tables, outer_at, inner_at = _grid_plan(
            kernel, live, outer, bh, t, block_q, block_k,
            *(((t // block_q, k_steps), k_block) if outer == "q"
              else ((tk // block_k, q_steps), q_block)))

        def specs(blocks):
            return [pl.BlockSpec((1, rows, width),
                                 outer_at if follows_outer else inner_at)
                    for rows, width, follows_outer in blocks]

        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        return pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=len(tables), grid=grid,
                in_specs=[smem, smem, *specs(blocks)],
                out_specs=tuple(specs(outs)), scratch_shapes=scratch),
            interpret=interpret, **params,
        )(*(_vary_like(jnp.asarray(table), union) for table in tables),
          q_off, k_off, *operands)

    # (rows, width, follows the outer axis) of a block in the k-outer grids
    # (the dk/dv call's and the fused one's) and in the dq call's q-outer one
    k_kv, v_kv = (block_k, d, True), (block_k, dv, True)
    kv_blocks = [k_kv, v_kv, (block_q, d, False), (block_q, dv, False),
                 (block_q, 1, False), (block_q, 1, False)]
    kv_operands = (kf, vf, qf, dof, lsef, dsumf)
    kv_acc = [
        pltpu.VMEM((block_k, d), jnp.float32),
        pltpu.VMEM((block_k, dv), jnp.float32),
    ]

    if form == "fused":
        # the names begin as the dq call's do: a reader that counts passes
        # by ``flash_attention[_window]_bwd_dq`` counts this call once, with
        # the whole pass's time
        dq, dk, dv_ = call(
            functools.partial(
                _bwd_fused_kernel, scale=scale, block=block_k, **dkv_window),
            "k", kv_operands, kv_blocks,
            # dq's block follows the OUTER axis: q-block kj is complete, and
            # written, at the first live step of k-block kj
            [k_kv, k_kv, v_kv],
            [pltpu.VMEM((t // block_q, block_q, d), jnp.float32), *kv_acc],
            out_shape=(sds((bh, t, d), q.dtype), sds((bh, tk, d), k.dtype),
                       sds((bh, tk, dv), v.dtype)),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=max(
                    fused_vmem_bytes(t, d, block_k, q.dtype.itemsize, dv),
                    VMEM_DEFAULT_BYTES)),
            name=name + "dq_dkv",
        )
    else:
        q_dq = (block_q, d, True)
        dq, = call(
            functools.partial(
                _bwd_dq_kernel,
                scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                **dq_window,
            ),
            "q", (qf, kf, vf, dof, lsef, dsumf),
            [q_dq, (block_k, d, False), (block_k, dv, False),
             (block_q, dv, True), (block_q, 1, True), (block_q, 1, True)],
            [q_dq], [pltpu.VMEM((block_q, d), jnp.float32)],
            out_shape=(sds((bh, t, d), q.dtype),),
            name=name + "dq",
        )

        dk, dv_ = call(
            functools.partial(
                _bwd_dkv_kernel,
                scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                **dkv_window,
            ),
            "k", kv_operands, kv_blocks, [k_kv, v_kv], kv_acc,
            out_shape=(sds((bh, tk, d), k.dtype), sds((bh, tk, dv), v.dtype)),
            name=name + "dkv",
        )

    return (
        dq.reshape(b, h, t, d),
        dk.reshape(b, h, tk, d),
        dv_.reshape(b, h, tk, dv),
    )


def pick_blocks(t_q: int, t_k: int, head_dim: int | None = None,
                itemsize: int = 2, value_dim: int | None = None) -> tuple:
    """Largest power-of-two blocks (≤1024 each) dividing the sequence
    lengths. Measured on TPU v5e at T=8k/head_dim 128: 1024×1024 runs the
    fwd+bwd pair ~1.4x faster than the old 512×1024 caps (26.5→18.4ms per
    layer — the BACKWARD kernel wants the larger q tile) with forward a
    touch faster too, and still beats both the einsum reference and jax's
    bundled flash kernel; 2048 tiles fail to compile (VMEM). Tiny sequences
    just clamp to themselves.

    ``head_dim`` tunes the cap to the lane width: the 1024 cap was measured
    at D=128 (one lane-width), and the VMEM footprint of a tile scales with
    block·D — so past 128 the cap halves per doubling of D, keeping the
    tile footprint (and the compile success envelope) constant.
    ``itemsize`` does the same for the operands' width: the cap was
    measured with bf16 operands, and float32 ones (a model traced at full
    precision for a check) double a tile's bytes — 1024-row float32 tiles
    at D=128 ask the backward kernel for 19.5 MB of its 16 MB of VMEM.
    ``value_dim``: the width of v and o where it is not q's and k's (keys
    of 192 over values of 128): a tile's bytes go by the two widths' sum."""

    # a head narrower than the 128 lanes is padded to them in VMEM: float32
    # heads of 64 at 1024 rows asked the forward kernel for 16.44 MB (PR 31)
    cap = 1024
    lanes = _lanes(head_dim or 128) + _lanes(value_dim or head_dim or 128)
    while cap > 128 and cap * lanes * itemsize > 1024 * 256 * 2:
        cap //= 2

    def _block(t, cap):
        b = cap
        while b > 1 and t % b:
            b //= 2
        return b

    return _block(t_q, cap), _block(t_k, cap)


def _reference(q, k, v, causal):
    # single source of truth for exact attention (the gradcheck oracle; must
    # stay in lockstep with the parallel layer)
    from raydp_tpu.parallel.ring_attention import full_attention

    return full_attention(q, k, v, causal=causal)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(
    q, k, v, causal: bool = False, block_q: int | None = None,
    block_k: int | None = None, interpret: bool | None = None,
    window: int | None = None,
):
    """Fused attention: q, k [B, H, T, D], v [B, H, T, Dv] → [B, H, T, Dv]
    (``Dv`` is ``D`` in every family but latent attention's, whose keys of
    192 stand over values of 128; o and its cotangent have v's width,
    forward and backward). ``block_q`` /
    ``block_k`` default to ``pick_blocks`` (measured-fastest large tiles);
    pass explicit sizes only to pin a tiling (tests / VMEM-constrained
    shard_map bodies). ``window`` (causal only): a query sees its own
    position and the ``window - 1`` before it. THE GRID FOLLOWS THE WINDOW
    in the forward and the backward kernels: the blocks it hides are not
    stepped over and not fetched (``window_steps``), and the calls are named
    ``flash_attention_window_fwd`` / ``_bwd_dq_dkv``. None, or a window of
    the whole sequence or more, is the causal call as it was. The backward
    pass is one fused call wherever ``backward_form`` says so."""
    return _flash_forward(q, k, v, causal, block_q, block_k, interpret,
                          window)


# the two residuals only the forward kernel can give, by the names a
# ``jax.checkpoint`` policy saves them under (``save_only_these_names``): a
# recomputed block that keeps both has no use for a second forward call.
# Outside such a policy a name is the identity. ``lse`` [B, H, T] and not the
# kernel's own ``m`` and ``l``: their [B*H, T, 1] results may be padded
# 128-fold in HBM.
SAVED_RESIDUALS = ("attn_out", "attn_lse")


def _fwd(q, k, v, causal, block_q, block_k, interpret, window=None):
    o, m, l = _flash_call(  # noqa: E741
        q, k, v, 0, 0, causal, block_q, block_k, interpret, normalize=True,
        window=window,
    )
    # residuals are O(T): inputs + normalized output + per-row logsumexp
    o = checkpoint_name(o, SAVED_RESIDUALS[0])
    lse = checkpoint_name(
        m + jnp.log(jnp.maximum(l, 1e-30)), SAVED_RESIDUALS[1]
    )
    return o, (q, k, v, o, lse)


def _bwd(causal, block_q, block_k, interpret, window, residuals, g):
    q, k, v, o, lse = residuals
    return _flash_backward(
        q, k, v, o, lse, g, causal, block_q, block_k, interpret, window
    )


flash_attention.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# decode: a few new query rows against a KV cache. Same online-softmax
# update as the prefill kernel (deferred rescale + deferred normalization),
# same masking predicate (keep k_pos <= q_pos), same NEG_INF/p-zeroing
# semantics — so a decode step at a fixed shape repeats, row for row, the
# arithmetic of a prefill pass over the same (dequantized) cache when
# block_k agrees. Grid is (batch·head, k-block) with per-sequence valid
# lengths in SMEM; k-blocks entirely past a sequence's length are skipped.
# ---------------------------------------------------------------------------


def _decode_body(
    kv_len_ref, q_ref, load_kv, o_ref, o_acc, m_acc, l_acc,
    *, scale, block_k, heads, tq,
):
    """Shared decode kernel body. ``load_kv()`` materializes this k-block's
    [BK, D] f32 K and V (identity for f32/bf16 caches, per-row dequant for
    int8) — kept behind a thunk so dead blocks skip the dequant too."""
    from jax.experimental import pallas as pl

    bh = pl.program_id(0)
    ki = pl.program_id(1)
    num_k = pl.num_programs(1)
    kv_len = kv_len_ref[bh // heads]

    @pl.when(ki == 0)
    def _init():
        o_acc[:] = jnp.zeros_like(o_acc)
        m_acc[:] = jnp.full_like(m_acc, NEG_INF)
        l_acc[:] = jnp.zeros_like(l_acc)

    @pl.when(ki * block_k < kv_len)
    def _accumulate():
        q = q_ref[0]  # [TQ, D] — last TQ positions of the sequence
        k, v = load_kv()  # [BK, D] f32 each
        scores = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [TQ, BK]

        # global positions: query rows are the last TQ positions (front
        # padding, if any, lands on negative q_pos and masks to nothing)
        q_pos = kv_len - tq + jax.lax.broadcasted_iota(
            jnp.int32, (tq, block_k), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (tq, block_k), 1
        )
        scores = jnp.where(q_pos >= k_pos, scores, NEG_INF)

        m_prev = m_acc[:, :1]
        l_prev = l_acc[:, :1]
        block_max = jnp.max(scores, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, block_max)
        p = jnp.exp(scores - m_new)
        p = jnp.where(scores > NEG_INF / 2, p, 0.0)

        p_sum = jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        moved = jnp.any(block_max > m_prev)

        @pl.when(moved)
        def _rescale():
            alpha = jnp.exp(m_prev - m_new)
            l_acc[:] = jnp.broadcast_to(alpha * l_prev + p_sum, l_acc.shape)
            o_acc[:] = alpha * o_acc[:] + pv

        @pl.when(jnp.logical_not(moved))
        def _no_rescale():
            l_acc[:] = jnp.broadcast_to(l_prev + p_sum, l_acc.shape)
            o_acc[:] = o_acc[:] + pv

        m_acc[:] = jnp.broadcast_to(m_new, m_acc.shape)

    @pl.when(ki == num_k - 1)
    def _finalize():
        o_ref[0] = (
            o_acc[:] / jnp.maximum(l_acc[:, :1], 1e-30)
        ).astype(o_ref.dtype)


def _decode_kernel(
    kv_len_ref, q_ref, k_ref, v_ref, o_ref, o_acc, m_acc, l_acc,
    *, scale, block_k, heads, tq,
):
    def load_kv():
        return (
            k_ref[0].astype(jnp.float32),
            v_ref[0].astype(jnp.float32),
        )

    _decode_body(
        kv_len_ref, q_ref, load_kv, o_ref, o_acc, m_acc, l_acc,
        scale=scale, block_k=block_k, heads=heads, tq=tq,
    )


def _decode_kernel_int8(
    kv_len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
    o_acc, m_acc, l_acc, *, scale, block_k, heads, tq,
):
    def load_kv():
        # per-row dequant on the fly (rows = cache positions): int8 values
        # carry a [BK, 1] f32 scale each for K and V — the layout
        # ops.quantization.quantize_int8 emits
        return (
            k_ref[0].astype(jnp.float32) * ks_ref[0],
            v_ref[0].astype(jnp.float32) * vs_ref[0],
        )

    _decode_body(
        kv_len_ref, q_ref, load_kv, o_ref, o_acc, m_acc, l_acc,
        scale=scale, block_k=block_k, heads=heads, tq=tq,
    )


def flash_decode(
    q, k, v, kv_len, *, k_scale=None, v_scale=None,
    block_k: int | None = None, interpret: bool | None = None,
):
    """Decode attention: the last ``Tq`` query rows of each sequence attend
    a KV cache with per-sequence valid lengths.

    q: [B, H, Tq, D] — queries for the newest Tq positions (usually 1).
    k, v: [B, H, Tk, D] — cache at fixed capacity Tk (f32/bf16; or int8
        with ``k_scale``/``v_scale`` [B, H, Tk] per-row scales from
        ``ops.quantization.quantize_int8``).
    kv_len: [B] int32 — valid lengths INCLUDING the Tq new positions.

    Returns [B, H, Tq, D] normalized attention output. Positions at or past
    ``kv_len`` are masked; k-blocks entirely past a sequence's length are
    skipped. Tq is padded up to the 8-sublane tile at the FRONT (pad rows
    get out-of-range q_pos and are sliced off), so callers can pass Tq=1.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = pallas_interpret(interpret)
    int8_kv = k_scale is not None
    if int8_kv != (v_scale is not None):
        raise ValueError("k_scale and v_scale must be provided together")
    b, h, tq, d = q.shape
    tk = k.shape[2]
    block_k = min(
        block_k
        or pick_blocks(tq, tk, head_dim=d, itemsize=q.dtype.itemsize)[1],
        tk,
    )
    if tk % block_k:
        raise ValueError(f"cache capacity {tk} must divide block_k {block_k}")

    tq_pad = max(8, -(-tq // 8) * 8)
    if tq_pad != tq:
        q = jnp.concatenate(
            [jnp.broadcast_to(q[:, :, :1], (b, h, tq_pad - tq, d)), q], axis=2
        )
    bh = b * h
    qf = q.reshape(bh, tq_pad, d)
    kf = k.reshape(bh, tk, d)
    vf = v.reshape(bh, tk, d)
    kv_len_arr = jnp.asarray(kv_len, jnp.int32).reshape(b)

    kernel_kwargs = dict(scale=d**-0.5, block_k=block_k, heads=h, tq=tq_pad)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_spec = pl.BlockSpec((1, tq_pad, d), lambda b_, j: (b_, 0, 0))
    kv_spec = pl.BlockSpec((1, block_k, d), lambda b_, j: (b_, j, 0))
    scale_spec = pl.BlockSpec((1, block_k, 1), lambda b_, j: (b_, j, 0))

    if int8_kv:
        kernel = functools.partial(_decode_kernel_int8, **kernel_kwargs)
        in_specs = [smem, q_spec, kv_spec, kv_spec, scale_spec, scale_spec]
        operands = (
            kv_len_arr, qf, kf, vf,
            k_scale.reshape(bh, tk, 1).astype(jnp.float32),
            v_scale.reshape(bh, tk, 1).astype(jnp.float32),
        )
    else:
        kernel = functools.partial(_decode_kernel, **kernel_kwargs)
        in_specs = [smem, q_spec, kv_spec, kv_spec]
        operands = (kv_len_arr, qf, kf, vf)

    o = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((bh, tq_pad, d), q.dtype),
        grid=(bh, tk // block_k),
        in_specs=in_specs,
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((tq_pad, d), jnp.float32),
            pltpu.VMEM((tq_pad, 128), jnp.float32),
            pltpu.VMEM((tq_pad, 128), jnp.float32),
        ],
        interpret=interpret,
    )(*operands)
    return o.reshape(b, h, tq_pad, d)[:, :, tq_pad - tq:]
