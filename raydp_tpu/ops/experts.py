"""Routed experts, ONE CHIP'S SHARE of an expert-parallel group: the layer is
told which experts it holds (``first``, and as many as its weights stack),
routes every token over ALL the experts, computes its own experts' part of
the result and leaves the rest out. On one chip it runs without the group's
exchange; nothing here stands in for the absent chips.

::

    z = r W_g                                [N, E], float32 (highest); r
                                             is u unless the caller hands
                                             the router another input
    scoring "sigmoid":  s = sigmoid(z); sel = top_k(s + b)   b enters the
                        w = s[sel] / (sum s[sel] + 1e-6)     SELECTION only
    scoring "softmax":  sel = top_k(z);  w = softmax(z[sel])    no bias
    groups G, kept g:   (sigmoid) the E experts in G groups of E / G; a
                        group's score the sum of its 2 largest s + b; the
                        top_k is taken inside the g best groups only
    w = w x scaling                          over the k selected, held here
                                             or not
    excess_e = pairs chosen for e / (N k / E) - 1    over ALL E, for the
                                             balancing rule (with a bias)
    out[n] = sum_{e in sel[n], e held} w[n, e] W2_e(act(W1_e u_n) W3_e u_n)

``act`` is ``silu`` (SwiGLU) or ``relu`` (ReGLU). The scoring rule and the
activation are static arguments a model's configuration chooses
(``SCORINGS``, ``ACTIVATIONS``), not knobs.

The (token, choice) pairs whose expert is held are ordered by expert (one
stable sort of the pairs' keys), their tokens gathered into rows, the rows
run through two GROUPED matrix products over the ragged groups (``W1 | W3``
stacked ``[held, D, 2F]``, then ``W2`` ``[held, F, D]``; operands in the
compute dtype, float32 accumulation), and each row is scaled by its weight
and added back to its token in float32 (the result in the tokens' dtype).
NO PAIR IS DROPPED for balance: there is no capacity factor.

THE ROWS' BUFFER FOLLOWS THE LOAD. Shapes are static and the load is not:
tokens x k rows (every choice held) are always enough, and a layer that
holds ``held`` of ``E`` experts fills ``held / E`` of them at the even load
the balancing rule keeps. Every pass over the buffer outside the grouped
kernels (the dispatch's gather, the activation, the cotangents' gathers)
costs the same filled or not, so the layer cuts the buffer to the LIKELY
BOUND, ``likely_row_bound``: ``SLACK`` x the even share, a whole number of
row tiles, computed from what the layer is handed (tokens, k, the experts
its weights stack, the router's width) and set by no caller. The sort runs
first, at the worst-case bound (int32 keys, under 1 ms), and says how many
rows are filled; everything after it (``_rows_pass``) is one function of a
static bound, run under ``lax.cond`` at the likely bound where the filled
rows fit it, AND AT THE WORST-CASE BOUND WHERE THEY DO NOT (the overflow's
path, ``report["full_bound"]`` 1): nothing is dropped for any input, and
the two arms do the same arithmetic on the same rows. A layer that holds
every expert has no smaller likely bound and no conditional. The
conditional has a backward pass of its own (``_rows_pass_by_load`` says
why). ``row_bound=`` given by the caller keeps its meaning: that bound, no
fallback, pairs beyond it left out AND COUNTED (``report["dropped"]``), so
that a caller who chose a smaller bound can hold the count to zero.

Both directions of both moves are GATHERS (``_rows_of_tokens``,
``_tokens_of_rows``), never a scatter-add with repeated indices (XLA:TPU
serialises those): the sort gives the permutation and its inverse, and a
third sort gives the held pairs IN TOKEN ORDER. The rows' side reads "row r
is token tok[r]". The token side, "token n is the sum of its held rows" (the
combine, and the dispatch's backward pass), is one sum in one of two forms
(``_sum_per_token``), and the shapes say which runs (``token_ordered``): a
gather on a TPU costs by the rows it produces, so the form that gathers
fewer. PER CHOICE: k gathers of N rows, ``rows[rank[:, j]]``, the choices
not held masked: k x N rows for the rows that are held. TOKEN-ORDERED: one
gather of the buffer's rows into token order, one pass that adds to each
slot the next k - 1 slots of the same token (float32, left to right: the
per-choice sum with its ``+ 0.0`` left out, the same bits), one gather of N
rows, each token's first slot: bound + N rows. Where this chip holds one
choice in four the buffer is a fraction of k x N and the second form runs;
at the worst-case bound (every expert held, the overflow's arm) the buffer
IS k x N rows and the first does, as it did before there were two. With the
second form the combine's weights take their gradient on the rows' side too
(a dot a row beside the pass that computes the rows' cotangent, then one
gather of a scalar a pair): no row is gathered for it.

WHERE THE EXPERTS SIT (``place``). A router with a bias is evened by the
balancing rule above. One without (the softmax rule) is not, and what a
group then chooses is the placement: ``place(loads, chips)`` deals a layer's
experts to the chips by the load observed, so that every chip holds near
the even share; ``models.hybridlm.HybridLM.placed_by_load`` makes a model's
seeded routers score the experts so placed.

The grouped product is ``impl="ragged_dot"`` (``lax.ragged_dot``: XLA:TPU
runs it as a Mosaic kernel of its own, ``%ragged-dot-*`` in a trace, and
XLA:CPU expands it) or ``impl="megablox"``
(``jax.experimental.pallas.ops.tpu.megablox``: ``%*gmm*`` / ``%*tgmm*``);
both visit only the row tiles the groups fill, whatever the bound.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from raydp_tpu import obs
from raydp_tpu.ops import backend

IMPLS = ("ragged_dot", "megablox")
# the router's two scoring rules and the experts' two gate activations
SCORINGS = ("sigmoid", "softmax")
ACTIVATIONS = {"silu": jax.nn.silu, "relu": jax.nn.relu}
# rows are visited a tile at a time: the bound is a multiple of it
ROW_TILE = 512
LANES = 128  # columns of a tile
MEGABLOX_TILING = (ROW_TILE, 1024, 1024)  # rows, contraction, columns
# the rows' buffer is cut to SLACK x the even share of the held experts (the
# LIKELY bound; a load past it runs the worst-case bound, so SLACK decides
# time and never a result). Per expert layer the held experts' pairs over the
# even share, 3 seeds x 40 steps x 4 layers from seeded parameters under the
# cell's balancing rule and warm-up (benchmark/tools/moe_load_drift.py, my
# chip run, PR 35): at most 1.165 in a fit's first step, at most 1.047 from
# its fourth step on. 1.25 clears both; a step alone took 673.0 ms at 1.25,
# 679.0 at 1.5, 694.8 at 2 and 740.1 at the worst-case bound (4 x even),
# all before ``_by_load``'s barrier
SLACK = 1.25
# the share of the experts at which SLACK was read (8 of 32 and 16 of 64 held:
# both routed cells hold a quarter). The held load is a sum over the held
# experts, so its spread over its mean grows as the share shrinks, by
# share^-1/2 (tokens that route alike widen it and keep the law), and
# ``likely_row_bound`` widens SLACK's margin by that factor below this share:
# 8 held of 512 at 8192 tokens x 8 (1,024 pairs at the even load) spread by
# 14 % of the even load from a fit's fourth step on; 1.25's 1,536 rows were
# passed by one layer in three consecutive epochs of one window of three
# (my chip runs, PR 49), the 2,048 this rule gives by none in seven windows
SLACK_SHARE = 0.25
# the name under which a recomputed block keeps the layer's discrete part,
# int32: every token's choice (``route``) and what the sorts made of it
# (``plan``: four numbers a (token, choice) pair, one a token)
KEPT = "experts_perm"


@jax.custom_vjp
def _hand_bias(w, bias, excess):
    """``w`` as it is. On the way back ``bias`` is handed ``excess`` AS ITS
    GRADIENT, whatever ``w``'s cotangent is: the bias enters a top-k and so
    has no gradient of the loss, and the rule that moves it (auxiliary-
    loss-free balancing, ``b_e -= rate x excess_e``) is the optimizer's to
    apply (``models.hybridlm.hybridlm_optimizer``), so the layer's word on
    the load leaves it the way every parameter's does, with no fetch."""
    return w


def _hand_bias_fwd(w, bias, excess):
    return w, excess


def _hand_bias_bwd(excess, d_w):
    return d_w, excess, jnp.zeros_like(excess)


_hand_bias.defvjp(_hand_bias_fwd, _hand_bias_bwd)


def route(u, w_gate, bias, top_k: int, scaling: float = 1.0,
          scoring: str = "sigmoid", groups: int = 0, groups_kept: int = 0,
          weight_eps: float = 1e-6):
    """(sel int32 [N, k], w float32 [N, k]) of tokens ``u`` [N, D].
    ``scoring="sigmoid"``: sigmoid scores over all of ``w_gate``'s experts,
    the k largest of score + bias, the selected scores normalised over the
    k (their sum + ``weight_eps``). ``groups`` > 0 (sigmoid only): the
    selection is GROUP-LIMITED (DeepSeek-V3's ``noaux_tc``): the experts lie
    in ``groups`` groups of consecutive ids, a group's score is the sum of
    its 2 largest score + bias, and the k are the largest inside the
    ``groups_kept`` best groups. ``scoring="softmax"``: the k largest
    LOGITS (no bias: ``bias`` is None), a softmax over the selected k. All
    of it float32, the product at ``highest``: a bf16 product moves a logit
    by 2e-3, and the 4th and 5th scores of a token lie closer than that
    often enough."""
    logits = jnp.dot(u.astype(jnp.float32), w_gate.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    if scoring == "softmax":
        if bias is not None or groups:
            raise ValueError("softmax over the selected takes no bias and "
                             "no group limit")
        _, sel = lax.top_k(logits, top_k)
        sel = checkpoint_name(sel.astype(jnp.int32), KEPT)  # as below
        w = jax.nn.softmax(jnp.take_along_axis(logits, sel, axis=-1), axis=-1)
        return sel, w * scaling
    if scoring != "sigmoid":
        raise ValueError(f"scoring {scoring!r} is not one of {SCORINGS}")
    scores = jax.nn.sigmoid(logits)
    biased = scores + lax.stop_gradient(bias)
    if groups:
        n, experts = biased.shape
        if experts % groups or not 0 < groups_kept <= groups:
            raise ValueError(f"{groups_kept} of {groups} groups over "
                             f"{experts} experts")
        grouped = biased.reshape(n, groups, experts // groups)
        best, _ = lax.top_k(grouped, 2)
        _, kept = lax.top_k(best.sum(axis=-1), groups_kept)
        open_ = jnp.any(kept[:, :, None] == jnp.arange(groups), axis=1)
        biased = jnp.where(open_[:, :, None], grouped, -jnp.inf).reshape(
            n, experts)
    _, sel = lax.top_k(biased, top_k)
    # THE CHOICE IS KEPT, with the permutations made from it (``KEPT``): a
    # recomputed block that chose again could choose otherwise for a token
    # whose k-th and (k+1)-th scores nearly tie (another fusion rounds
    # otherwise), and rows sorted by one choice under groups sized by the
    # other are garbage (gradients 1e5 times too large; my chip run, PR 34)
    sel = checkpoint_name(sel.astype(jnp.int32), KEPT)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + weight_eps) * scaling
    return sel, w


def excess_load(sel, experts: int):
    """float32 [experts]: the pairs of ``sel`` int32 [N, k] that chose each
    of ALL the experts, over the even share (N k / experts), less 1."""
    chosen = jnp.sum(sel[..., None] == jnp.arange(experts, dtype=jnp.int32),
                     axis=(0, 1), dtype=jnp.float32)
    return chosen * (experts / sel.size) - 1.0


def place(loads, chips: int) -> tuple:
    """A group's PLACEMENT of one layer's experts by observed load, on the
    host: ``loads`` [E], the pairs that chose each expert -> ``order``, a
    permutation of the E experts in which chip c of ``chips`` holds
    ``order[c x E / chips : (c + 1) x E / chips]``. The heaviest expert
    first, each to the chip with the least load so far that still has a
    free slot; then, while it narrows the gap, the one swap of an expert
    between the fullest and the emptiest chip that narrows it most. Every
    chip ends near the even share wherever no single expert is most of a
    chip's (a router WITHOUT a bias has no rule that evens its load: where
    the experts sit is what a group has left to choose). Ties go to the
    lower expert and the lower chip: the same loads, the same order."""
    loads = [float(v) for v in loads]
    per = len(loads) // chips
    if per * chips != len(loads):
        raise ValueError(f"{len(loads)} experts over {chips} chips")
    held, total = [[] for _ in range(chips)], [0.0] * chips
    for e in sorted(range(len(loads)), key=lambda e: (-loads[e], e)):
        c = min((c for c in range(chips) if len(held[c]) < per),
                key=lambda c: (total[c], c))
        held[c].append(e)
        total[c] += loads[e]
    while True:
        hi = max(range(chips), key=lambda c: (total[c], -c))
        lo = min(range(chips), key=lambda c: (total[c], c))
        gap = total[hi] - total[lo]
        # moving d = loads[a] - loads[b] from hi to lo leaves |gap - 2 d|
        swaps = [(abs(gap - 2 * (loads[a] - loads[b])), a, b)
                 for a in held[hi] for b in held[lo]]
        left, a, b = min(swaps) if swaps else (gap, None, None)
        if not left < gap:
            break
        held[hi][held[hi].index(a)], held[lo][held[lo].index(b)] = b, a
        total[hi] -= loads[a] - loads[b]
        total[lo] += loads[a] - loads[b]
    return tuple(e for chip in held for e in sorted(chip))


def row_bound_for(pairs: int) -> int:
    """Rows of a buffer that holds ``pairs`` pairs: a whole number of row
    tiles. tokens x k pairs is the worst case (every choice held) and
    always correct."""
    return -(-max(pairs, 1) // ROW_TILE) * ROW_TILE


def plan(sel, first: int, count: int, row_bound: int):
    """The sort. ``sel`` int32 [N, k] -> a dict of

    ``tok``    int32 [R]     the token row r reads (rows past ``rows`` read
                             some token or other: never used)
    ``pair``   int32 [R]     the (token, choice) pair of row r, flat
    ``rank``   int32 [N, k]  the row of a pair (clipped into the buffer)
    ``valid``  bool [N, k]   the pair's expert is held AND its row is
                             inside the bound
    ``sizes``  int32 [count] rows of each held expert inside the bound
    ``load``   int32 [count] pairs routed to each held expert
    ``rows``   int32 []      rows filled (the sum of ``sizes``)
    ``dropped`` int32 []     held pairs past the bound

    and THE HELD PAIRS IN TOKEN ORDER (by their flat index n k + j: token by
    token, a token's choices in the order they are summed in), slot by slot:

    ``by_token``      int32 [R]  the row of slot i
    ``pair_by_token`` int32 [R]  the pair of slot i, flat; N k past the
                                 filled slots, so ``// k`` gives a slot's
                                 token and N, no token's, past them
    ``head``          int32 [N]  a token's first slot (where it has none:
                                 the slot the next one's would be)
    """
    n, k = sel.shape
    local = sel - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)
    pairs = lax.iota(jnp.int32, n * k)
    # stable: inside an expert's group the pairs keep the tokens' order
    _, order = lax.sort((key, pairs), num_keys=1, is_stable=True)
    _, rank = lax.sort((order, pairs), num_keys=1)  # the inverse permutation
    # what a sort gives is KEPT beside the choice it was made from, and what
    # follows is made of what is kept: a recomputed block does not sort again
    order = checkpoint_name(order, KEPT)
    rank = checkpoint_name(rank.reshape(n, k), KEPT)
    load = jnp.sum(key[:, None] == jnp.arange(count, dtype=jnp.int32),
                   axis=0, dtype=jnp.int32)
    ends = jnp.minimum(jnp.cumsum(load), row_bound)
    sizes = ends - jnp.concatenate([jnp.zeros((1,), jnp.int32), ends[:-1]])
    valid = held & (rank < row_bound)
    rank = jnp.minimum(rank, row_bound - 1)
    # the pairs that are not valid go last: the keys of those that are differ
    slot_pair, slot_row = lax.sort(
        (jnp.where(valid, pairs.reshape(n, k), n * k).reshape(-1),
         rank.reshape(-1)), num_keys=1, is_stable=False)
    slots = jnp.sum(valid, axis=1, dtype=jnp.int32)
    slot_pair, slot_row, head = (checkpoint_name(a, KEPT) for a in (
        slot_pair, slot_row, jnp.cumsum(slots) - slots))

    def fit(a, fill=0):
        # a bound rounded up past the pairs there are: rows nobody fills
        return jnp.pad(a, (0, max(0, row_bound - n * k)),
                       constant_values=fill)[:row_bound]

    order = fit(order)
    return {
        "tok": order // k, "pair": order, "rank": rank, "valid": valid,
        "sizes": sizes, "load": load, "rows": ends[-1],
        "dropped": jnp.sum(load) - ends[-1],
        "by_token": fit(slot_row), "pair_by_token": fit(slot_pair, n * k),
        "head": head,
    }


# -- the two moves, gathers in both directions ---------------------------------


def token_ordered(bound: int, tokens: int, k: int) -> bool:
    """Whether the token side's sums run token-ordered at ``bound`` rows:
    where that form gathers fewer rows (bound + tokens) than a gather a
    choice does (k x tokens). Shapes decide, at trace time."""
    return bound + tokens < k * tokens


def token_rows_gathered(bound: int, tokens: int, k: int) -> int:
    """Rows a token-side sum gathers at ``bound`` rows, in the form
    ``token_ordered`` says it runs in."""
    return bound + tokens if token_ordered(bound, tokens, k) else k * tokens


def _sum_per_token(rows, q, weight=None):
    """[N, D] in ``rows``'s dtype: each token's sum, in float32, over its k
    choices in their order of the row the choice went to (x ``weight``
    [N, k]), the choices not ``valid`` left out; rounded once. ``q`` is a
    plan's arrays at ``rows``'s row count (``_plan_at``)."""
    n, k = q["rank"].shape
    bound, d = rows.shape
    if not token_ordered(bound, n, k):
        total = None
        for j in range(k):
            part = rows[q["rank"][:, j]].astype(jnp.float32)
            if weight is not None:
                part = part * weight[:, j, None]
            part = jnp.where(q["valid"][:, j, None], part, 0.0)
            total = part if total is None else total + part
        return total.astype(rows.dtype)
    # ONE TILE A ROW, [bound, D / 128, 128], from the first gather to the
    # last: a row gathered is then one piece of memory and not sixteen (0.67
    # ms for 40,960 rows of 2048 against 1.42 as [bound, 2048], whose 16-row
    # tiles hold 128 columns of a row each), and the row axis is a major
    # one, so a shift along it is an offset (a one-row shift of [bound,
    # 2048] is a relayout); the copy into that form takes 0.51 ms (one
    # expert layer at the cell's shapes, _scratch probes, my chip runs, PR 40)
    tiled = (bound, d // LANES, LANES) if d % LANES == 0 else (bound, d)
    over = (slice(None),) + (None,) * (len(tiled) - 1)  # a number a slot

    def ahead_of(a, ahead, fill=0):
        return jnp.pad(a[ahead:], (0, ahead), constant_values=fill)

    # k - 1 slots past the last, for the shifts to run into (never added:
    # no token is theirs)
    g = rows.reshape(tiled)[jnp.pad(q["by_token"], (0, k - 1))]
    slot_weight = None if weight is None else weight.reshape(-1)[
        jnp.minimum(q["pair_by_token"], n * k - 1)]
    token = q["pair_by_token"] // k
    # a slot takes the k - 1 slots after it that are its token's, left to
    # right: the sum per choice with its ``+ 0.0`` left out. Only a token's
    # FIRST slot is read below (a later one holds a tail of the sum; past the
    # filled slots, whatever the kernel left in the unfilled rows). THE
    # SHIFTS START AT AN OFFSET XLA CANNOT FOLD (a token's first slot is an
    # exclusive sum, so the first token's is 0, at run time): a slice at a
    # constant offset XLA:TPU makes a copy of (0.51 ms each, three a sum),
    # one at an offset it reads inside the pass that adds (1.19 ms, all four)
    zero = jnp.minimum(q["head"][0], 0)
    total = None
    for ahead in range(k):
        part = lax.dynamic_slice_in_dim(g, zero + ahead, bound).astype(
            jnp.float32)
        if slot_weight is not None:
            part = part * ahead_of(slot_weight, ahead)[over]
        if ahead:
            same = ahead_of(token, ahead, -1) == token
            part = jnp.where(same[over], part, 0.0)
        total = part if total is None else total + part
    out = total.astype(rows.dtype)[q["head"]].reshape(n, d)
    return jnp.where(q["valid"].any(axis=1)[:, None], out, 0)


@jax.custom_vjp
def _rows_of_tokens(u, q):
    """Dispatch: row r is token ``tok[r]``'s features."""
    return u[q["tok"]]


def _rows_of_tokens_fwd(u, q):
    return u[q["tok"]], q


def _rows_of_tokens_bwd(q, d_rows):
    return _sum_per_token(d_rows, q), None


_rows_of_tokens.defvjp(_rows_of_tokens_fwd, _rows_of_tokens_bwd)


@jax.custom_vjp
def _tokens_of_rows(y, w, q):
    """Combine: token n is the sum of its valid choices' rows, each scaled by
    the choice's weight, in float32; in the rows' dtype."""
    return _sum_per_token(y, q, w)


def _tokens_of_rows_fwd(y, w, q):
    return _sum_per_token(y, q, w), (y, w, q)


def _tokens_of_rows_bwd(kept, d_out):
    y, w, q = kept
    n, k = q["rank"].shape
    # the cotangent a ROW: gathered in the rows' dtype, which the result it
    # is the cotangent of has (float32 rows would be written for every row of
    # the buffer, filled or not: 5.0 ms a layer at 131,072 x 2048; my chip
    # run, PR 34)
    d_row = d_out[q["tok"]].astype(jnp.float32)
    d_y = jnp.where(q["row_valid"][:, None],
                    d_row * w.reshape(-1)[q["pair"]][:, None], 0.0)
    if token_ordered(y.shape[0], n, k):
        # a weight's gradient is its row's dot with that cotangent: taken
        # where the rows are, beside ``d_y``, and handed to the pair by one
        # gather of a scalar (an unfilled row's is whatever the kernel left
        # there, and no valid pair's)
        d_w_row = jnp.sum(y.astype(jnp.float32) * d_row, axis=-1)
        d_w = jnp.where(q["valid"], d_w_row[q["rank"]], 0.0)
    else:
        # per choice, a row gathered a choice. At tokens x k rows the dot
        # beside ``d_y`` keeps ``d_row`` for a second reader, so ``d_y``
        # cannot take its buffer: 0.54 GB more held by the epoch program
        # (compiled for a described v5e)
        d_w = jnp.stack([
            jnp.where(q["valid"][:, j],
                      jnp.sum(y[q["rank"][:, j]].astype(jnp.float32)
                              * d_out.astype(jnp.float32), axis=-1), 0.0)
            for j in range(k)], axis=1)
    return d_y.astype(y.dtype), d_w.astype(w.dtype), None


_tokens_of_rows.defvjp(_tokens_of_rows_fwd, _tokens_of_rows_bwd)


# -- the grouped product -------------------------------------------------------


# one expert layer forward + backward at the cell's shapes (32,768 tokens of
# 2048, 8 experts of 1792 held of 32, top-4, 29,296 pairs held, rows' bound
# 131,072; benchmark/tools/moe_layer_bench.py, my chip run, PR 34): megablox
# 54.0 ms a call, its six kernel calls 14.2 ms (69 % of the pairs' 9.8 ms of
# needed FLOPs); ragged_dot 57.2 ms, its kernels 17.0 ms (58 %). Both are
# over a third of their roofline, so no kernel of the repo's own; megablox on
# the chip. Off it the Pallas interpreter does not run megablox's data-sized
# grid, and XLA:CPU expands ragged_dot: the CPU tests run that.
# With float32 operands (the benchmark's ``matched`` run) megablox's tiles
# ask for 18 MB of the 16 MB of VMEM, forward at 1024 x 1024 and backward
# at 512 x 1024 alike, and XLA's kernel is right to 8e-7 at ``highest``
# (benchmark/tools/moe_layer_check.py, my chip run, PR 34): ragged_dot there.
IMPL_WHY = ("megablox for 2-byte operands on a TPU (69 % of roofline against "
            "58 %), ragged_dot for float32 ones and off a TPU")


def default_impl(dtype=jnp.bfloat16) -> str:
    """What ``impl=None`` means for operands of ``dtype`` (``IMPL_WHY``)."""
    narrow = jnp.dtype(dtype).itemsize <= 2
    return "megablox" if backend.on_tpu() and narrow else "ragged_dot"


def grouped_dot(x, w, sizes, impl: str | None = None):
    """``x[rows of group g] @ w[g]`` for each group: ``x`` [R, K] ordered by
    group, ``w`` [G, K, N], ``sizes`` int32 [G] (their sum may be under R:
    rows past it come back as whatever the kernel left there, and the
    caller masks them). Operands in ``x``'s dtype, float32 accumulation,
    the result in ``x``'s dtype."""
    impl = impl or default_impl(x.dtype)
    w = w.astype(x.dtype)
    if impl == "ragged_dot":
        return lax.ragged_dot(x, w, sizes, preferred_element_type=x.dtype)
    if impl == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        return megablox.gmm(x, w, sizes, x.dtype, MEGABLOX_TILING,
                            interpret=backend.pallas_interpret())
    raise ValueError(f"grouped product {impl!r} is not one of {IMPLS}")


# -- the layer -----------------------------------------------------------------


def likely_row_bound(pairs: int, held: int, experts: int) -> int:
    """Rows of the buffer a layer that holds ``held`` of ``experts`` experts
    runs at for ``pairs`` (token, choice) pairs, almost always: ``SLACK``
    times the even share (its margin over 1 wider by (``SLACK_SHARE`` /
    share)^1/2 where the share held is under ``SLACK_SHARE``), a whole
    number of row tiles, never over the worst case (which it is when every
    expert is held)."""
    wider = max(1.0, math.sqrt(SLACK_SHARE * experts / held))
    slack = 1.0 + (SLACK - 1.0) * wider
    return min(row_bound_for(math.ceil(slack * pairs * held / experts)),
               row_bound_for(pairs))


def _plan_at(p, bound: int):
    """What the two moves read of a plan ``p``, AT ``bound`` ROWS (the rows
    it was made for, or fewer where every row it fills lies inside them):
    the per-row and per-slot lists cut to ``bound``, what points into them
    clipped, and ``row_valid`` bool [bound], the rows that are filled."""
    cut = {key: p[key][:bound] for key in ("tok", "pair", "pair_by_token")}
    clipped = {key: jnp.minimum(p[key], bound - 1)
               for key in ("rank", "head")}
    return {**cut, **clipped, "valid": p["valid"],
            "by_token": jnp.minimum(p["by_token"][:bound], bound - 1),
            "row_valid": lax.iota(jnp.int32, bound) < p["rows"]}


def _rows_pass(bound: int, impl, scope: str, act: str, u, w, w13, w2, p):
    """Everything after the plan AT ``bound`` ROWS: dispatch, the two grouped
    products with the activation ``act`` between them, combine; [N, D] in
    ``u``'s dtype. ``p`` is a plan made at ``bound`` rows or more; at fewer rows
    than it was made for, every row it fills must lie inside ``bound`` (the
    caller's predicate), so ``valid`` and ``sizes`` hold as they are."""
    two_f = w13.shape[2]
    q = _plan_at(p, bound)
    with obs.device_scope(f"{scope}.dispatch"):
        x = _rows_of_tokens(u, q)
    with obs.device_scope(f"{scope}.gmm"):
        h = grouped_dot(x, w13, p["sizes"], impl)
        gate, up = h[:, :two_f // 2], h[:, two_f // 2:]
        # rows past the groups hold whatever the kernel left: zeroed here,
        # in the pass that computes the activation anyway
        a = jnp.where(q["row_valid"][:, None], ACTIVATIONS[act](gate) * up, 0)
        y = grouped_dot(a.astype(u.dtype), w2, p["sizes"], impl)
    with obs.device_scope(f"{scope}.combine"):
        # in the tokens' dtype, which the rows have: a conditional's result
        # is a buffer of its own, and a float32 one is written and read again
        return _tokens_of_rows(y, w, q)


def _by_load(likely: int, p, at_bound, *operands):
    """``at_bound(bound, *operands)`` at ``likely`` rows where the plan's
    filled rows fit them, else at the rows the plan was made for. The
    results leave through a barrier: without it XLA moves their consumers'
    first elementwise step into both arms, and the arms return float32
    buffers beside the results (the residual stream's sum forward, a product
    of AdamW's on each expert weight's gradient backward): 672.2 ms a step
    alone for 656.5 behind the barrier (my chip run, PR 35)."""
    return lax.optimization_barrier(lax.cond(
        p["rows"] <= likely, functools.partial(at_bound, likely),
        functools.partial(at_bound, p["tok"].shape[0]), *operands))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3))
def _rows_pass_by_load(likely: int, impl, scope: str, act: str, u, w, w13, w2,
                       p):
    """``_rows_pass`` at ``likely`` rows where the load fits and at the
    plan's own (worst-case) bound where it does not. ITS BACKWARD PASS IS
    ITS OWN: autodiff through ``lax.cond`` returns BOTH branches' residuals
    from the forward conditional, the branch not taken as zeros (x, h, a, y
    at 131,072 rows: 2.5 GB of zeros a layer written by the branch that was
    to spare them, and both sets held). Here the forward keeps its inputs
    and nothing else, and the backward pass chooses its branch by the same
    predicate and differentiates ``_rows_pass`` INSIDE the branch (one
    forward of it there, which a recomputed block runs anyway: a block
    that keeps the layer's output, as ``HybridLM``'s does, runs the pass
    once forward and once in the backward pass, as before)."""
    return _by_load(
        likely, p, lambda bound, *a: _rows_pass(bound, impl, scope, act, *a),
        u, w, w13, w2, p)


def _rows_pass_by_load_fwd(likely, impl, scope, act, u, w, w13, w2, p):
    return (_rows_pass_by_load(likely, impl, scope, act, u, w, w13, w2, p),
            (u, w, w13, w2, p))


def _rows_pass_by_load_bwd(likely, impl, scope, act, kept, d_out):
    *inputs, p = kept

    def back(bound, u, w, w13, w2, p, d_out):
        _, pull = jax.vjp(
            lambda *a: _rows_pass(bound, impl, scope, act, *a, p),
            u, w, w13, w2)
        return pull(d_out)

    return (*_by_load(likely, p, back, *inputs, p, d_out), None)


_rows_pass_by_load.defvjp(_rows_pass_by_load_fwd, _rows_pass_by_load_bwd)


def routed_experts(u, w_gate, bias, w13, w2, *, first: int, top_k: int,
                   scaling: float = 1.0, row_bound: int | None = None,
                   impl: str | None = None, scope: str = "experts",
                   scoring: str = "sigmoid", activation: str = "silu",
                   router_input=None, groups: int = 0, groups_kept: int = 0,
                   weight_eps: float = 1e-6):
    """This chip's part of the routed experts' result for tokens ``u``
    [N, D]: ``(out [N, D] in ``u``'s dtype, report)``.

    ``w_gate`` [D, E] and ``bias`` [E] are the router's, over ALL E experts;
    ``w13`` [held, D, 2F] (gate | up) and ``w2`` [held, F, D] the experts
    ``first .. first + held - 1``. ``scoring``: ``"sigmoid"`` (sigmoid
    scores, the k largest of score + ``bias``, normalised over the k) or
    ``"softmax"`` (the k largest logits, a softmax over the selected;
    ``bias`` None). ``activation``: the experts' gate's, ``"silu"`` or
    ``"relu"``. ``router_input`` [N, D]: what the router reads in ``u``'s
    place (a model whose router is fed from the block's input, before its
    attention); the experts read ``u`` either way. ``groups``,
    ``groups_kept``, ``weight_eps``: ``route``'s. ``row_bound=None``: the
    layer chooses
    (``likely_row_bound`` where the batch's load fits it, tokens x k where
    it does not: nothing dropped, whatever the load). ``row_bound`` given:
    that bound and no other, pairs past it left out and counted.
    ``report``: ``sel`` int32 [N, k] (every token's choice), ``load``
    float32 [held] (pairs routed to each held expert), ``dropped`` float32
    [] (held pairs past ``row_bound``: zero where the layer chose),
    ``full_bound`` float32 [] (1 where the worst-case bound ran BECAUSE the
    load overflowed the likely one, else 0). ``bias`` comes back from a
    backward pass with ``excess_load`` in its gradient's place
    (``_hand_bias``)."""
    if activation not in ACTIVATIONS:
        raise ValueError(f"activation {activation!r} is not one of "
                         f"{tuple(ACTIVATIONS)}")
    n, _ = u.shape
    count = w13.shape[0]
    total = w_gate.shape[1]
    if first < 0 or first + count > total:
        raise ValueError(
            f"experts {first}..{first + count - 1} are not among the "
            f"router's {total}")
    worst = row_bound_for(n * top_k)
    likely = likely_row_bound(n * top_k, count, total)
    if row_bound is not None:
        worst = likely = row_bound
    with obs.device_scope(f"{scope}.route"):
        sel, w = route(u if router_input is None else router_input, w_gate,
                       bias, top_k, scaling, scoring, groups=groups,
                       groups_kept=groups_kept, weight_eps=weight_eps)
        if bias is not None:
            # the bias's "gradient": every expert's excess load
            w = _hand_bias(w, bias, excess_load(sel, total))
    with obs.device_scope(f"{scope}.dispatch"):
        p = plan(sel, first, count, worst)
    report = {"sel": sel, "load": p.pop("load").astype(jnp.float32),
              "dropped": p.pop("dropped").astype(jnp.float32),
              "full_bound": (p["rows"] > likely).astype(jnp.float32)}
    # the experts' weights in the tokens' dtype BEFORE the conditional: their
    # gradients leave it in that dtype, and the cast back to the parameters'
    # fuses into the optimizer's update as it did without a conditional
    w13, w2 = w13.astype(u.dtype), w2.astype(u.dtype)
    if likely < worst:
        out = _rows_pass_by_load(likely, impl, scope, activation, u, w, w13,
                                 w2, p)
    else:  # every expert held, or a bound given: one program, no conditional
        out = _rows_pass(worst, impl, scope, activation, u, w, w13, w2, p)
    return out, report
