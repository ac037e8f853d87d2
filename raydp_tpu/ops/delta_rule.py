"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) in its chunked
form: with a decay a head, plain ``jnp`` forward and autodiff's backward
pass (``gated_delta_rule``); with a decay a CHANNEL, one Pallas kernel
forward and one backward (``channel_gated_delta_rule``).

Per head, with a state ``S`` [Dv, Dk], a decay ``alpha_t`` in (0, 1) and a
step ``beta_t`` in (0, 2)::

    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T    S_0 = 0
    o_t = S_t q_t

Each token first ERASES what the state holds in its key's direction and then
writes; ``ops/ssd.py``'s state only ever adds. With the pseudo-value ``u_t =
beta_t (v_t - alpha_t S_{t-1} k_t)`` the update is ``S_t = alpha_t S_{t-1} +
u_t k_t^T``, a decayed sum as the state-space scan's, but ``u_t`` depends on
every earlier ``u`` of the chunk: over a chunk of ``C`` tokens that enters
with the state ``S_in`` (the WY / UT form)::

    g_i     = sum_{j <= i} log alpha_j                     inside the chunk
    L[i, j] = exp(g_i - g_j)  for j <= i, else 0            the masked decays
    M       = diag(beta) (strict(L) o K K^T)                strictly lower
    T       = (I + M)^-1                                    unit lower triangular
    U       = T diag(beta) V - T diag(beta exp(g)) K S_in^T
    O       = diag(exp(g)) Q S_in^T + (L o Q K^T) U
    S_out   = exp(g_C) S_in + U^T diag(exp(g_C - g)) K

A chunk is products and ONE unit-lower-triangular inverse of size ``C``; the
states between chunks go through a serial recurrence ``T / C`` steps long,
two products a step (``U`` needs the entering state).

Every decay is built from DIFFERENCES of the running sums ``g`` (``g_i -
g_j`` with j <= i, ``g_i - 0``, ``g_C - g_j``), never as a quotient of
cumulative products: with Mamba-2's initialisation of ``A_log`` and
``dt_bias`` a chunk's ``sum log alpha`` passes -100, where ``exp(g)`` is 0 in
float32 and a quotient 0 / 0. Decays, ``beta``, the triangular inverse and
the state are float32; the products' operands are ``q``'s dtype (bf16 on the
chip) with float32 accumulation: ``ops/ssd.py``'s rule.

A DECAY A CHANNEL (``channel_gated_delta_rule``; Kimi Delta Attention,
arXiv:2510.26692): ``alpha_t`` is a vector over the key's ``Dk`` channels and
the state's column d decays by ``alpha_t[d]``::

    S_t = S_{t-1} Diag(alpha_t) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T

The chunk's algebra is the same with ``G_i = sum_{j <= i} log alpha_j`` a
vector: ``K exp(G)`` where ``exp(g) K`` stood, ``K exp(G_C - G)`` at the
chunk's end. But the decay no longer multiplies the chunk's two score
matrices from outside; it sits INSIDE their contraction::

    KK[i, j] = sum_d k_i[d] k_j[d] exp(G_i[d] - G_j[d])    (QK alike)

and the factorisation ``(K exp(G)) (K exp(-G))^T`` overflows float32 over a
chunk (a log-decay down to -5 a token is -320 over 64 tokens). So the chunk
is cut into SUB-BLOCKS of ``SUB`` tokens and a sub-block's rows of both
matrices are products through the sub-block's MIDDLE row r: ``(x_i exp(G_i -
G_r)) . (k_j exp(G_r - G_j))``. For the keys before r the second exponent
is <= 0 (the rule above); inside the sub-block either exponent lies within
``SUB / 2`` tokens' decay of 0, BOUNDED and not <= 0: at a floor of -5 a
token at most e^40 against float32's e^88 (``LOG_DECAY_FLOOR`` = -10 a token
and channel is the lowest the form bears, and what ``HybridLM`` holds
``kda_decay_floor`` to; Kimi Delta Attention's is -5). Every other decay of
the channel form has a non-positive exponent, as above. And no exponent of
the channel form is a difference of two running sums: each ``G_a - G_j`` is
the signed SUM of the log-decays of the tokens between a and j (``_between``:
the chunk's start for ``exp(G)``, its end for ``exp(G_C - G)``, a
sub-block's middle row), so it is rounded at its OWN size and not at the
running sum's (float32 holds -320 to 3e-5, and a decay is as wrong as its
exponent: against a float64 recurrence the form read 5e-7 with differences
of running sums and reads 1.5e-7 with sums between). A sub-block against
ITSELF is a product of float32 operands (its decays lie inside the
contraction, so an operand rounded to bf16 would be a decay rounded to
bf16: the gradient of a fast decay, a difference of near-equal sums, then
reads 0.5 off where it reads 0.004); against the sub-blocks before it the
operands are ``q``'s dtype.

WHERE THE CHANNEL FORM'S DATA LIVES. The two calls (``delta_rule_fwd``,
``delta_rule_bwd``) read ``q``, ``k``, ``v``, ``log_alpha`` as ``[b, t, h *
d]`` with a head's channels one block column, and ``beta`` as ``[b, t, h]``
(a step picks its head's column): a reshape of what the mixer holds, no
head-major copy; a caller that holds that flat form already
(``ops/kda_mixer.py``'s kernels write it) hands it over as it is and gets
``o`` back flat. The grid is (batch, heads by ``HEADS_A_STEP``, token tile),
the tiles of ``CHUNKS_A_STEP`` chunks in order. Of a tile, ``_chunk_matrices``
first works every chunk of the step's heads at once (the running sums,
``QK``, ``KK``, the inverse, ``U0 = T beta V`` and ``W = T beta K exp(G)``:
independent products, so the MXU takes one behind the other), then the
chunks go one after the other through the head's state ``[Dv, Dk]`` float32
(two products a chunk, the step's heads side by side), which lives in VMEM
scratch from the head's first tile to its last and never goes to HBM; so do
a chunk's matrices. ``o`` is written once.

THE BACKWARD CALL keeps nothing of the forward call but its operands
(``custom_vjp``'s residuals are q, k, v, log_alpha, beta; a caller whose
recomputed block keeps ``SAVED_OUTPUT`` therefore runs no second forward
call). Its grid is (batch, heads by ``HEADS_A_STEP``, pass, token tile).
Pass 0 runs the STATE ALONE through the heads' tiles in order and keeps, in
VMEM scratch, the state that enters each tile (``t / (C x CHUNKS_A_STEP)``
states a head) and every chunk's inverse (``t / C`` matrices of C x C a
head: 4 MB at 8192 tokens; ``vmem_bytes`` says what the call asks for). Pass
1 takes the tiles in reverse: the tile's chunks go through the state again
from the tile's entering state (a chunk's entering state and U stay in
scratch), then through the state's gradient last chunk first (``dS`` in
VMEM scratch from tile to tile), then all at once to ``dq``, ``dk``, ``dv``,
``dlog_alpha``, ``dbeta``, each written once. No state a chunk is written
to HBM.

The float32 rule inside the kernels: the sums of log-decays are products
of a matrix of 0 and +-1 with the log-decays in three bfloat16 passes whose
every product is EXACT (a float32 is the sum of three bfloat16 and the signs
are exact), and the log-decays' gradient is the transposed products; the
inverse is built from products of float32 operands at ``Precision.HIGHEST``
(the diagonal blocks of ``SUB`` rows by the finite series (I - D)(I + D^2)(I
+ D^4)(I + D^8), then two blocks at a time), and so are its argument's
gradient (``-dR U^T``), a sub-block's scores against itself ([2 SUB, SUB],
beside the operands' dtype product against the keys before it) and their
gradients, and the sums over a row that ``dbeta`` is; every other product
takes its operands in ``q``'s dtype, at the precision of the trace's context
(``highest`` in a float32 check). Off the chip the same bodies run through
the Pallas interpreter (``ops/backend.py``); under a mesh each device runs
the calls on its rows of the batch (``backend.per_shard``: XLA cannot
partition a Mosaic call). WHAT THE CHIP DOES NOT BEAR and the interpreter
does is refused by name (``_refused``): heads that are not whole 128-lane
tiles, several devices and no mesh, a sequence whose kept inverses and
states pass the VMEM a call may ask for (``vmem_bytes``: 74 MiB at 8192
tokens, past ``VMEM_ASK_BOUND_BYTES`` from 32k on).

The whole region runs under the device scope ``delta_rule``
(``obs.device_scope``), whichever entry point, forward and backward, and its
result carries the ``checkpoint_name`` ``SAVED_OUTPUT``, so that a trace
reader and a save-by-name ``jax.checkpoint`` policy can find it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from raydp_tpu import obs
from raydp_tpu.ops.backend import (
    VMEM_ASK_BOUND_BYTES, pallas_interpret, per_shard_under_mesh,
    unpartitioned)

SCOPE = "delta_rule"
SAVED_OUTPUT = "delta_out"
# what runs the scan, by what the decay is one of (``log_alpha``'s rank: 3 a
# head, 4 a channel): what ``HybridLM.fit_facts`` says under ``delta.scan``
SCAN = {"head": "plain", "channel": "kernel"}
# tokens a chunk: the triangular solve is C x C a head and chunk, the serial
# recurrence T / C steps long. 64 is the usual one
CHUNK = 64
# tokens a sub-block of the channel-decay form: its rows of the chunk's
# decayed scores are one product through its middle row, and the inverse's
# diagonal blocks are so many rows
SUB = 16


def recurrence_flops(tokens: int, heads: int, key_dim: int,
                     value_dim: int) -> int:
    """FLOPs the RECURRENCE needs forward, whatever implements it: a token
    and head decays and erases (``S k``: 2 Dk Dv), writes (the rank-one
    update: 2 Dk Dv) and reads out (``S q``: 2 Dk Dv)."""
    return 6 * key_dim * value_dim * heads * tokens


def _unit_lower_inverse(m):
    """T = (I + M)^-1 for strictly lower triangular ``m`` [..., C, C],
    float32: ONE unit-lower-triangular solve a head and chunk."""
    eye = jnp.eye(m.shape[-1], dtype=m.dtype)
    return lax.linalg.triangular_solve(
        eye + m, jnp.broadcast_to(eye, m.shape), left_side=True, lower=True,
        unit_diagonal=True)


def gated_delta_rule(q, k, v, log_alpha, beta, chunk: int = CHUNK):
    """``q``, ``k`` [b, t, h, dk] (as the recurrence takes them: normalised
    and scaled by the caller); ``v`` [b, t, h, dv]; ``log_alpha`` [b, t, h]
    (<= 0) and ``beta`` [b, t, h], float32. Returns ``o`` [b, t, h, dv] in
    ``q``'s dtype. ``chunk`` must divide ``t`` (a sequence shorter than a
    chunk is one chunk)."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    c = min(int(chunk), t)
    if t % c:
        raise ValueError(f"chunk {c} does not divide the sequence length {t}")
    n = t // c
    dtype, f32 = q.dtype, jnp.float32
    with obs.device_scope(SCOPE):
        def chunked(x):  # [b, t, h, ...] -> [b, n, h, c, ...], head-major
            x = x.reshape((b, n, c, h) + x.shape[3:])
            return jnp.moveaxis(x, 3, 2)

        qc, kc, vc = chunked(q), chunked(k), chunked(v)
        bc = chunked(beta.astype(f32))
        g = jnp.cumsum(chunked(log_alpha.astype(f32)), axis=-1)  # [b,n,h,c]

        # inside a chunk: token j reaches token i >= j through alpha_{j+1..i}
        keep = jnp.tril(jnp.ones((c, c), bool))
        decays = jnp.exp(jnp.where(
            keep, g[..., :, None] - g[..., None, :], -jnp.inf))
        kk = jnp.einsum("bnhid,bnhjd->bnhij", kc, kc,
                        preferred_element_type=f32)
        qk = jnp.einsum("bnhid,bnhjd->bnhij", qc, kc,
                        preferred_element_type=f32)
        strict = jnp.tril(jnp.ones((c, c), bool), -1)
        m = bc[..., None] * jnp.where(strict, decays * kk, 0.0)
        inverse = _unit_lower_inverse(m).astype(dtype)
        u0 = jnp.einsum("bnhij,bnhjd->bnhid", inverse,
                        (bc[..., None] * vc.astype(f32)).astype(dtype),
                        preferred_element_type=f32)
        w = jnp.einsum("bnhij,bnhjd->bnhid", inverse,
                       ((bc * jnp.exp(g))[..., None]
                        * kc.astype(f32)).astype(dtype),
                       preferred_element_type=f32)
        # a chunk's keys decayed to the chunk's end: what its U writes
        k_end = (jnp.exp(g[..., -1:] - g)[..., None]
                 * kc.astype(f32)).astype(dtype)

        # the state that ENTERS each chunk: the one serial part
        def carry_on(state, chunk_in):
            u0_c, w_c, k_c, decay = chunk_in
            u = u0_c - jnp.einsum("bhid,bhvd->bhiv", w_c.astype(dtype),
                                  state.astype(dtype),
                                  preferred_element_type=f32)
            out = decay[..., None, None] * state + jnp.einsum(
                "bhiv,bhid->bhvd", u.astype(dtype), k_c,
                preferred_element_type=f32)
            return out, (state, u)

        def chunk_major(x):
            return jnp.moveaxis(x, 1, 0)

        _, (entering, u) = lax.scan(
            carry_on, jnp.zeros((b, h, dv, dk), f32),
            (chunk_major(u0), chunk_major(w), chunk_major(k_end),
             chunk_major(jnp.exp(g[..., -1]))))
        entering, u = jnp.moveaxis(entering, 0, 1), jnp.moveaxis(u, 0, 1)

        o = jnp.einsum("bnhij,bnhjv->bnhiv", (qk * decays).astype(dtype),
                       u.astype(dtype), preferred_element_type=f32)
        o = o + jnp.exp(g)[..., None] * jnp.einsum(
            "bnhid,bnhvd->bnhiv", qc, entering.astype(dtype),
            preferred_element_type=f32)
        o = jnp.moveaxis(o, 2, 3).reshape(b, t, h, dv).astype(dtype)
        return checkpoint_name(o, SAVED_OUTPUT)




# ---------------------------------------------------------------------------
# A decay a channel: one Pallas kernel forward, one backward

F32 = jnp.float32
# [m, k] x [k, n], [m, k] x [n, k] and [k, m] x [k, n]; with a leading axis
# on both, one product a leading index
_NN, _NT, _TN = (1, 0), (1, 1), (0, 0)
# what is float32 BY RULE (running sums, the inverse, sums over a row) is
# float32 whatever precision the trace's context gives the operands' products
_EXACT = lax.Precision.HIGHEST
# chunks a grid step where a head has more: a step's rows of ``dbeta`` are a
# block [chunks, c], whose sublanes go in eights
CHUNKS_A_STEP = 8
# the lowest log-decay a token and channel the channel form bears: a
# sub-block's operands are decayed from its middle row, an exponent of up to
# ``SUB / 2`` tokens' decay (80 here, against float32's 88)
LOG_DECAY_FLOOR = -80.0 / (SUB // 2)
# scoped VMEM the calls ask for beside what grows with the sequence
VMEM_BYTES = 32 * 2**20


def _dot(a, b, dims, precision=None):
    lead = a.ndim - 2
    return lax.dot_general(
        a, b, (((dims[0] + lead,), (dims[1] + lead,)),
               (tuple(range(lead)),) * 2),
        precision=precision, preferred_element_type=F32)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _triangle(like, keep):
    """``keep(rows, cols)`` over the last two axes of ``like`` [..., c, c]."""
    return keep(_iota(like.shape, like.ndim - 2),
                _iota(like.shape, like.ndim - 1))


def _cols(*parts):
    return jnp.concatenate(parts, axis=-1)


def _split(x):
    """``x`` float32 [..., c, d] as the three bfloat16 it is the sum of, side
    by side [..., c, 3 d]: its leading 8 bits, the next 8, the last 8."""
    parts, rest = [], x
    for _ in range(3):
        parts.append(rest.astype(jnp.bfloat16))
        rest = rest - parts[-1].astype(F32)
    return _cols(*parts)


def _signs(lead, rows: int, c: int, anchor: int):
    """[..., rows, c] bfloat16: +1 at (j, l) where token l lies after j up to
    the anchor (j < l <= anchor), -1 where it lies after the anchor up to j."""
    shape = tuple(lead) + (rows, c)
    j, token = _iota(shape, len(lead)), _iota(shape, len(lead) + 1)
    return (jnp.where((j < token) & (token <= anchor), 1.0, 0.0)
            - jnp.where((anchor < token) & (token <= j), 1.0, 0.0)
            ).astype(jnp.bfloat16)


def _whole(sums):
    d = sums.shape[-1] // 3
    return sums[..., :d] + (sums[..., d:2 * d] + sums[..., 2 * d:])


def _between(split, rows: int, anchor: int):
    """``G_anchor - G_j`` for the chunk's first ``rows`` tokens j (G the
    running sums of the log-decays; ``anchor`` -1 is the chunk's start, G =
    0): the SUM of the log-decays of the tokens between the two, signed, as
    a product of ``_signs`` with the log-decays' ``_split`` [..., c, 3 dk]
    (three bfloat16 passes, every product exact). Never a difference of two
    running sums: the sums' float32 would cost the exponent the rounding of
    the whole sum (3e-5 at -320), a sum of what lies between costs its
    own."""
    return _whole(_dot(_signs(split.shape[:-2], rows, split.shape[-2], anchor),
                       split, _NN, lax.Precision.DEFAULT))


def _between_gradient(dx, c: int, anchor: int):
    """The log-decays' gradient [..., c, dk] from that of ``_between``'s
    result ``dx`` [..., rows, dk]: the transposed product."""
    return _whole(_dot(_signs(dx.shape[:-2], dx.shape[-2], c, anchor),
                       _split(dx), _TN, lax.Precision.DEFAULT))


def _row_sums(x):
    """The sums over each row of ``x`` [..., c, d] float32 AS A ROW [..., 1,
    c] (the lanes ``dbeta``'s block wants them on): a product with ones."""
    ones = jnp.ones(x.shape[:-2] + (8, x.shape[-1]), F32)
    return _dot(ones, x, _NT, _EXACT)[..., :1, :]


def _sub_block(qf, kf, split, i: int, s: int):
    """What sub-block ``i``'s rows of the chunks' decayed scores are a
    product of, float32: its rows of q and k [..., s, dk] decayed from its
    MIDDLE row ``r`` on (``since`` = exp(G_i - G_r): within ``s / 2``
    tokens of ``r`` either way, so bounded by the decays of so many tokens
    and not by 1) and every key of the chunk up to the sub-block's last
    [..., (i + 1) s, dk] decayed up to ``r`` (``until`` = exp(G_r - G_j):
    <= 0 the exponent before ``r``, bounded alike after it). ``split`` is
    the log-decays' (``_split``)."""
    lo, hi, r = i * s, (i + 1) * s, i * s + s // 2
    up_to_middle = _between(split, hi, r)
    since, until = jnp.exp(-up_to_middle[..., lo:, :]), jnp.exp(up_to_middle)
    return (since, qf[..., lo:hi, :] * since, kf[..., lo:hi, :] * since,
            until, kf[..., :hi, :] * until)


def _padded(x, size: int, axis: int):
    """``x`` with zeros after it up to ``size`` along ``axis`` (-1 or -2)."""
    if x.shape[axis] == size:
        return x
    shape = list(x.shape)
    shape[axis] = size - x.shape[axis]
    return jnp.concatenate([x, jnp.zeros(shape, x.dtype)], axis=axis)


def _rows(*parts):
    return jnp.concatenate(parts, axis=-2)


def _chunk_matrices(q, k, v, log_alpha, beta, s: int, inverse=None,
                    read_out: bool = True):
    """What a chunk is BEFORE any state enters it, for every chunk of a grid
    step at once (``q``, ``k`` [n, c, dk] and ``v`` [n, c, dv] in the
    operands' dtype, ``log_alpha`` [n, c, dk] and ``beta`` [n, c, 1]
    float32; the chunks' products are independent, so the MXU takes them one
    behind the other):

    - ``g``: the running log-decay sums G [n, c, dk];
    - ``qk`` (lower triangular with its diagonal), ``kk`` (strictly lower)
      [n, c, c]: ``[i, j] = sum_d x_i[d] k_j[d] exp(G_i[d] - G_j[d])``, a
      sub-block's rows one product (no ``qk`` and no ``qg`` without
      ``read_out``: the state alone needs neither);
    - ``inverse``: T = (I + beta KK)^-1 (computed unless given);
    - ``u0`` = T (beta V) [n, c, dv] and ``w`` = T (beta K exp(G)) [n, c,
      dk]: the chunk's pseudo-values are U = U0 - W S^T once the state S
      enters;
    - ``qg`` = Q exp(G), ``k_end`` = K exp(G_C - G) and ``at_end`` =
      exp(G_C) [n, 1, dk].

    All float32."""
    dtype, c = q.dtype, q.shape[-2]
    qf, kf = q.astype(F32), k.astype(F32)
    split = _split(log_alpha)
    qk, kk = [], []
    for i in range(c // s):
        lo = i * s
        _, q_since, k_since, _, k_until = _sub_block(qf, kf, split, i, s)
        since = _rows(q_since, k_since) if read_out else k_since
        # the sub-block against ITSELF in float32 (its decays lie inside
        # the contraction: rounding a decayed operand to bf16 would round
        # the decay), against the sub-blocks before it in the operands' dtype
        if i and dtype != F32:
            rows = _cols(
                _dot(since.astype(dtype), k_until[..., :lo, :].astype(dtype),
                     _NT),
                _dot(since, k_until[..., lo:, :], _NT, _EXACT))
        else:
            rows = _dot(since, k_until, _NT, _EXACT)
        rows = _padded(rows, c, -1)
        qk.append(rows[..., :-s, :])
        kk.append(rows[..., -s:, :])
    kk = _rows(*kk)
    kk = jnp.where(_triangle(kk, lambda r, c: r > c), kk, 0.0)
    qk = jnp.where(_triangle(kk, lambda r, c: r >= c), _rows(*qk),
                   0.0) if read_out else None
    if inverse is None:
        inverse = _unit_lower_inverse_by_blocks(beta * kk, s)
    g, to_end = -_between(split, c, -1), _between(split, c, c - 1)
    from_start = jnp.exp(g)
    solved = _dot(inverse.astype(dtype), _cols(
        beta * v.astype(F32), beta * kf * from_start).astype(dtype), _NN)
    return {"g": g, "to_end": to_end, "qk": qk, "kk": kk, "inverse": inverse,
            "u0": solved[..., :v.shape[-1]], "w": solved[..., v.shape[-1]:],
            "qg": qf * from_start if read_out else None,
            "k_end": kf * jnp.exp(to_end),
            "at_end": jnp.exp(g[..., c - 1:, :])}


def _unit_lower_inverse_by_blocks(m, s: int):
    """(I + M)^-1 for strictly lower triangular ``m`` [..., c, c], float32,
    in products alone: the diagonal blocks of ``s`` rows by the finite
    series (I - D)(I + D^2)(I + D^4)... (D is nilpotent: D^s = 0), then two
    neighbouring blocks at a time, [[A, 0], [C, B]]^-1 = [[A^-1, 0],
    [-B^-1 C A^-1, B^-1]], until one block is the chunk."""
    c = m.shape[-1]
    eye = jnp.where(_triangle(m, lambda r, c: r == c), 1.0, 0.0)
    power = jnp.where(_triangle(m, lambda r, c: r // s == c // s), m, 0.0)
    inverse = eye - power
    if s > 2:
        power = _dot(power, power, _NN, _EXACT)
    reach = 2
    while reach < s:
        # (I + P) inverse and P P from the same left operand (polynomials
        # in D commute): one product of twice the width
        if 2 * reach < s:
            both = _dot(power, _cols(inverse, power), _NN, _EXACT)
            inverse, power = inverse + both[..., :c], both[..., c:]
        else:
            inverse = inverse + _dot(power, inverse, _NN, _EXACT)
        reach *= 2
    size = s
    while size < c:
        below = _triangle(m, lambda r, c: (
            r // (2 * size) == c // (2 * size)) & (r // size != c // size))
        # only the rows of each pair's second block change: half the rows
        # go through the two products
        second = [slice(lo, lo + size) for lo in range(size, c, 2 * size)]
        lower = _dot(_dot(_rows(*(inverse[..., rows, :] for rows in second)),
                          jnp.where(below, m, 0.0), _NN, _EXACT),
                     inverse, _NN, _EXACT)
        inverse = _rows(*(
            part for i, rows in enumerate(second) for part in (
                inverse[..., rows.start - size:rows.start, :],
                inverse[..., rows, :]
                - lower[..., i * size:(i + 1) * size, :])))
        size *= 2
    return inverse


def _chunk_state(u0, w_qg, k_end, at_end, state, qk=None):
    """One chunk (of each head of a grid step: a leading axis) once its
    entering ``state`` [dv, dk] (float32) is there: ``u0`` [c, dv] float32;
    ``w_qg`` [2c, dk] (W over Q exp(G); W alone where no ``o`` is asked
    for), ``k_end`` [c, dk] and ``qk`` [c, c] in the operands' dtype. Two
    products lie between a chunk's state and the next one's: W S^T and U^T
    K_end. Returns (U float32, the state that leaves the chunk, and with
    ``qk`` the chunk's rows of ``o``)."""
    dtype, c = k_end.dtype, k_end.shape[-2]
    held = _dot(w_qg, state.astype(dtype), _NT)
    u = u0 - held[..., :c, :]
    leaving = state * at_end + _dot(u.astype(dtype), k_end, _TN)
    o = None if qk is None else held[..., c:, :] + _dot(
        qk, u.astype(dtype), _NN)
    return u, leaving, o


def _chunk_dstate(du0, ds0, w, k_end, at_end, dstate):
    """The part of a chunk's backward pass the NEXT (earlier) chunk waits
    for: ``dstate`` [dv, dk] float32 is the gradient of the state that
    leaves the chunk; ``du0`` = QK^T dO [c, dv] and ``ds0`` = dO^T (Q
    exp(G)) [dv, dk] float32 are what of dU and of the entering state's
    gradient no state is needed for. Two products again: K_end dS^T and
    dU^T W. Returns (dU, the entering state's gradient)."""
    dtype = w.dtype
    du = du0 + _dot(k_end, dstate.astype(dtype), _NT)
    return du, dstate * at_end + ds0 - _dot(du.astype(dtype), w, _TN)


def _chunk_gradients(q, k, v, log_alpha, beta, g, to_end, kk, inverse, u,
                     state, do, du, dstate, s: int):
    """The operands' gradients of every chunk of a grid step at once, from
    what the two serial passes left: ``state`` / ``dstate`` [n, dv, dk] the
    state that enters each chunk and the gradient of the one that leaves
    it, ``u`` and ``du`` [n, c, dv]; ``g`` and ``to_end`` [n, c, dk] the
    exponents ``_chunk_matrices`` gave. With E = V - (K exp(G)) S^T, U = T
    (beta E); with dR = T^T dU the inverse's gradient needs no product of
    its own: ``-T^T dT T^T = -dR U^T``. The log-decays' gradient is the sum
    of ``_between_gradient`` over every exponent formed. Returns (dq, dk,
    dv, dlog_alpha [n, c, .], dbeta [n, 1, c]), float32."""
    dtype, c = q.dtype, q.shape[-2]
    qf, kf = q.astype(F32), k.astype(F32)
    from_start, to_end = jnp.exp(g), jnp.exp(to_end)
    qg, kg, k_end = qf * from_start, kf * from_start, kf * to_end
    u_d, state_d = u.astype(dtype), state.astype(dtype)
    e = v.astype(F32) - _dot(kg.astype(dtype), state_d, _NT)
    dr = _dot(inverse.astype(dtype), du.astype(dtype), _TN)
    de = beta * dr
    dqk = _dot(do, u_d, _NT)
    dqk = jnp.where(_triangle(dqk, lambda r, c: r >= c), dqk, 0.0)
    dm = _dot(dr, u, _NT, _EXACT)
    dm = jnp.where(_triangle(dm, lambda r, c: r > c), -dm, 0.0)
    dkk = beta * dm
    dbeta = _row_sums(dr * e) + _row_sums(dm * kk)

    through = _dot(_rows(do, (-de).astype(dtype)), state_d, _NN)
    dqg, dkg = through[..., :c, :], through[..., c:, :]
    dk_end = _dot(u_d, dstate.astype(dtype), _NN)
    dq, dk = dqg * from_start, dkg * from_start + dk_end * to_end
    # exp(G_C) decays the state over the chunk: the last row's G
    at_last = _iota(g.shape[:-1] + (1,), g.ndim - 2) == c - 1
    dg = dqg * qg + dkg * kg + jnp.where(
        at_last, jnp.sum(dstate * state, axis=-2, keepdims=True)
        * from_start[..., c - 1:, :], 0.0)
    dla = (_between_gradient(-dg, c, -1)
           + _between_gradient(dk_end * k_end, c, c - 1))

    split = _split(log_alpha)
    dq_rows, dk_rows = [], []
    for i in range(c // s):
        lo, hi, r = i * s, (i + 1) * s, i * s + s // 2
        since, q_since, k_since, until, k_until = _sub_block(
            qf, kf, split, i, s)
        rows = _rows(q_since, k_since)
        d_rows = _rows(dqk[..., lo:hi, :hi], dkk[..., lo:hi, :hi])
        # as forward: the sub-block's own columns in float32, the columns
        # before it in the operands' dtype
        if i and dtype != F32:
            before = d_rows[..., :lo].astype(dtype)
            d_since = _dot(d_rows[..., lo:], k_until[..., lo:, :], _NN,
                           _EXACT) + _dot(
                before, k_until[..., :lo, :].astype(dtype), _NN)
            d_until = _rows(_dot(before, rows.astype(dtype), _TN),
                            _dot(d_rows[..., lo:], rows, _TN, _EXACT))
        else:
            d_since = _dot(d_rows, k_until, _NN, _EXACT)
            d_until = _dot(d_rows, rows, _TN, _EXACT)
        dq_rows.append(d_since[..., :s, :] * since)
        dk_rows.append(d_since[..., s:, :] * since)
        dk = dk + _padded(d_until * until, c, -2)
        # the exponent G_r - G_j: ``until`` takes it, ``since`` its negative
        own = d_since[..., :s, :] * q_since + d_since[..., s:, :] * k_since
        d_exponent = d_until * k_until
        dla = dla + _between_gradient(
            _rows(d_exponent[..., :lo, :], d_exponent[..., lo:, :] - own)
            if i else d_exponent - own, c, r)
    return (dq + _rows(*dq_rows), dk + _rows(*dk_rows), de, dla, dbeta)


# heads a grid step where the heads pair up: their chunks go through the
# serial passes side by side, two independent chains for the MXU
HEADS_A_STEP = 2


def _by_chunk(x, heads: int, c: int):
    """A ``[tile, heads x d]`` block as [heads x n, c, d]: a head's n chunks,
    then the next head's."""
    d = x.shape[1] // heads
    return jnp.concatenate([
        x[:, g * d:(g + 1) * d].reshape(x.shape[0] // c, c, d)
        for g in range(heads)])


def _step_operands(refs, beta_ref, first, heads: int, c: int):
    """A grid step's blocks by head and chunk (``_by_chunk``), and ``beta``'s
    columns for the step's heads (``first`` on) out of its ``[1, tile, h]``
    block (the model's layout keeps the heads on the lanes) as [heads x n,
    c, 1]."""
    every = beta_ref[0]
    beta = jnp.concatenate([
        jnp.sum(jnp.where(_iota(every.shape, 1) == first + g, every, 0.0),
                axis=1, keepdims=True) for g in range(heads)], axis=1)
    return [_by_chunk(x, heads, c)
            for x in [ref[0] for ref in refs] + [beta]]


def _by_head(ref, j: int, heads: int):
    """Chunk ``j`` of each head out of scratch [heads x n, ...]."""
    n = ref.shape[0] // heads
    return jnp.stack([ref[g * n + j] for g in range(heads)])


def _to_blocks(ref, x, heads: int):
    """[heads x n, c, d] into a ``[1, tile, heads x d]`` block."""
    n, d = x.shape[0] // heads, x.shape[2]
    for g in range(heads):
        ref[0, :, g * d:(g + 1) * d] = x[g * n:(g + 1) * n].reshape(
            ref.shape[1], d).astype(ref.dtype)


def _forward_kernel(q_ref, k_ref, v_ref, la_ref, beta_ref, o_ref, state_ref,
                    u0_s, w_qg_s, k_end_s, at_end_s, qk_s, *, c: int, s: int,
                    heads: int):
    """Grid (batch, heads by ``heads``, token tile), the tiles in order: the
    heads' states stay in ``state_ref`` from one tile to the next. A tile's
    chunks first all at once (``_chunk_matrices``, into scratch), then one
    after the other through the state."""
    @pl.when(pl.program_id(2) == 0)
    def _first():
        state_ref[...] = jnp.zeros_like(state_ref)

    dtype, dv = q_ref.dtype, v_ref.shape[2] // heads
    m = _chunk_matrices(*_step_operands(
        (q_ref, k_ref, v_ref, la_ref), beta_ref, pl.program_id(1) * heads,
        heads, c), s)
    u0_s[...] = m["u0"]
    at_end_s[...] = jnp.broadcast_to(m["at_end"], at_end_s.shape)
    w_qg_s[...] = _rows(m["w"], m["qg"]).astype(dtype)
    k_end_s[...], qk_s[...] = m["k_end"].astype(dtype), m["qk"].astype(dtype)

    state = state_ref[...]
    for j in range(q_ref.shape[1] // c):  # unrolled: a chunk's read-out
        # overlaps the next chunk's two products
        _, state, o = _chunk_state(*(
            _by_head(ref, j, heads) for ref in (u0_s, w_qg_s, k_end_s)),
            _by_head(at_end_s, j, heads)[:, :1], state,
            _by_head(qk_s, j, heads))
        for g in range(heads):
            o_ref[0, j * c:(j + 1) * c, g * dv:(g + 1) * dv] = o[g].astype(
                o_ref.dtype)
    state_ref[...] = state


def _backward_kernel(q_ref, k_ref, v_ref, la_ref, beta_ref, do_ref,
                     dq_ref, dk_ref, dv_ref, dla_ref, dbeta_ref,
                     state_ref, dstate_ref, tiles_s, inverses_s, u0_s, w_s,
                     k_end_s, at_end_s, du0_s, ds0_s, u_s, du_s, states_s,
                     dstates_s, *, c: int, s: int, heads: int):
    """Grid (batch, heads by ``heads``, pass, token tile). Pass 0 runs the
    state alone through the heads' tiles in order and keeps, in scratch, the
    state that ENTERS each tile and every chunk's inverse. Pass 1 takes the
    tiles in reverse: a tile's chunks go through the state again from the
    tile's entering state (each chunk's entering state and U stay in
    scratch), then through the state's gradient last chunk first
    (``dstate_ref`` carries it from tile to tile), and then all at once to
    the operands' gradients."""
    pair, sweep, step = (pl.program_id(axis) for axis in (1, 2, 3))
    tile = jnp.where(sweep == 0, step, pl.num_programs(3) - 1 - step)
    chunks, dtype = q_ref.shape[1] // c, q_ref.dtype
    q, k, v, log_alpha, beta = _step_operands(
        (q_ref, k_ref, v_ref, la_ref), beta_ref, pair * heads, heads, c)

    def keep(ref, j, x):
        for g in range(heads):
            ref[g * chunks + j] = x[g]

    def through_the_state(m, kept: bool):
        u0_s[...] = m["u0"]
        at_end_s[...] = jnp.broadcast_to(m["at_end"], at_end_s.shape)
        w_s[...], k_end_s[...] = m["w"].astype(dtype), m["k_end"].astype(dtype)

        state = state_ref[...]
        for j in range(chunks):
            if kept:
                keep(states_s, j, state)
            u, state, _ = _chunk_state(*(
                _by_head(ref, j, heads) for ref in (u0_s, w_s, k_end_s)),
                _by_head(at_end_s, j, heads)[:, :1], state)
            if kept:
                keep(u_s, j, u)
        state_ref[...] = state

    @pl.when(sweep == 0)
    def _states():
        @pl.when(step == 0)
        def _first():
            state_ref[...] = jnp.zeros_like(state_ref)

        tiles_s[tile] = state_ref[...]
        m = _chunk_matrices(q, k, v, log_alpha, beta, s, read_out=False)
        inverses_s[tile] = m["inverse"]
        through_the_state(m, False)

    @pl.when(sweep == 1)
    def _gradients():
        @pl.when(step == 0)
        def _last():
            dstate_ref[...] = jnp.zeros_like(dstate_ref)

        state_ref[...] = tiles_s[tile]
        m = _chunk_matrices(q, k, v, log_alpha, beta, s, inverses_s[tile])
        through_the_state(m, True)
        do = _by_chunk(do_ref[0], heads, c)
        du0_s[...] = _dot(m["qk"].astype(dtype), do, _TN)
        ds0_s[...] = _dot(do, m["qg"].astype(dtype), _TN)

        dstate = dstate_ref[...]
        for j in reversed(range(chunks)):
            keep(dstates_s, j, dstate)
            du, dstate = _chunk_dstate(*(
                _by_head(ref, j, heads) for ref in (
                    du0_s, ds0_s, w_s, k_end_s)),
                _by_head(at_end_s, j, heads)[:, :1], dstate)
            keep(du_s, j, du)
        dstate_ref[...] = dstate
        dq, dk, dv, dla, dbeta = _chunk_gradients(
            q, k, v, log_alpha, beta, m["g"], m["to_end"], m["kk"],
            m["inverse"], u_s[...],
            states_s[...], do, du_s[...], dstates_s[...], s)
        for ref, x in ((dq_ref, dq), (dk_ref, dk), (dv_ref, dv),
                       (dla_ref, dla)):
            _to_blocks(ref, x, heads)
        for g in range(heads):
            dbeta_ref[0, g] = dbeta[g * chunks:(g + 1) * chunks].reshape(
                chunks, c)


def grid_step(t: int, h: int, c: int):
    """(tokens, heads) a grid step of the two calls takes of ``t`` tokens in
    chunks of ``c`` and ``h`` heads."""
    chunks = t // c
    return (c * (CHUNKS_A_STEP if chunks % CHUNKS_A_STEP == 0 else chunks),
            HEADS_A_STEP if h % HEADS_A_STEP == 0 else 1)


def _layout(q, v, beta, c: int):
    """The calls' view of the operands: ``q`` [b, t, h * dk] and ``v`` [b,
    t, h * dv], a head's channels one block column (``beta`` [b, t, h] says
    how many heads), in grid steps of ``tile`` tokens and ``heads`` heads."""
    b, t, h = beta.shape
    return (b, t, h, q.shape[2] // h, v.shape[2] // h) + grid_step(t, h, c)


def _lanes(width: int) -> int:
    return -(-width // 128) * 128


def vmem_bytes(t: int, dk: int, dv: int, c: int = CHUNK,
               backward: bool = True) -> int:
    """Scoped VMEM a call asks for: the blocks and scratch of a grid step
    and, in the backward call, what a head keeps from its first pass: the
    state that enters each grid step and every chunk's inverse (lanes in
    128s)."""
    steps = max(t // (c * CHUNKS_A_STEP), 1)
    return HEADS_A_STEP * (VMEM_BYTES + backward * 4 * (
        steps * dv * _lanes(dk) + t * _lanes(c)))


def _params(semantics, vmem: int):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=vmem)


def _cost(q, v, beta, passes: int, gradients: bool):
    """The RECURRENCE's operations and bytes (``recurrence_flops`` once
    forward, twice more backward; every operand read and every result
    written once), not the chunked form's: what XLA's count of the program
    then holds for the call."""
    b, t, h = beta.shape
    dk, dv, size = q.shape[2] // h, v.shape[2] // h, q.dtype.itemsize
    rows = b * t * h
    read = rows * ((2 * dk + dv) * size + (dk + 1) * 4)
    wrote = rows * ((2 * dk + dv) * size + (dk + 1) * 4 if gradients
                    else dv * size)
    return pl.CostEstimate(
        flops=passes * b * recurrence_flops(t, h, dk, dv),
        transcendentals=passes * rows * dk,
        bytes_accessed=read + wrote + (rows * dv * size if gradients else 0))


# THE TWO CALLS' ENTRIES ARE EACH ONE ``jax.jit`` of the module: a model's
# layers of one shape share a trace of the kernel's body and one lowered
# function (a Mosaic call is traced and lowered once a layer and pass
# otherwise: 0.9 s a layer for this pair, ``PERF.md`` §6, PR 52). What a trace
# reads of the process (``pallas_interpret``) is an argument, so that the
# cache keeps the two answers apart; the scope and the ``checkpoint_name``
# stay with the callers, outside
@functools.partial(jax.jit, static_argnames=("c", "s", "interpret"))
def _forward_call(q, k, v, log_alpha, beta, *, c: int, s: int,
                  interpret: bool):
    b, t, h, dk, dv, tile, heads = _layout(q, v, beta, c)
    chunks = heads * tile // c

    def at(d):
        return pl.BlockSpec((1, tile, heads * d),
                            lambda bi, hi, ti: (bi, ti, hi))

    return pl.pallas_call(
        functools.partial(_forward_kernel, c=c, s=s, heads=heads),
        grid=(b, h // heads, t // tile),
        in_specs=[at(dk), at(dk), at(dv), at(dk),
                  pl.BlockSpec((1, tile, h), lambda bi, hi, ti: (bi, ti, 0))],
        out_specs=at(dv),
        out_shape=jax.ShapeDtypeStruct((b, t, h * dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((heads, dv, dk), F32),
            pltpu.VMEM((chunks, c, dv), F32),
            pltpu.VMEM((chunks, 2 * c, dk), q.dtype),
            pltpu.VMEM((chunks, c, dk), q.dtype),
            pltpu.VMEM((chunks, 8, dk), F32),
            pltpu.VMEM((chunks, c, c), q.dtype)],
        compiler_params=_params(("parallel", "parallel", "arbitrary"),
                                vmem_bytes(t, dk, dv, c, False)),
        cost_estimate=_cost(q, v, beta, 1, False),
        interpret=interpret,
        name="delta_rule_fwd",
    )(q, k, v, log_alpha, beta)


@functools.partial(jax.jit, static_argnames=("c", "s", "interpret"))
def _backward_call(q, k, v, log_alpha, beta, do, *, c: int, s: int,
                   interpret: bool):
    b, t, h, dk, dv, tile, heads = _layout(q, v, beta, c)
    tiles, chunks = t // tile, heads * tile // c

    # pass 0 walks the tiles up and pass 1 down; what only pass 1 touches
    # (do, the gradients) waits through pass 0 at pass 1's first block
    def walked(d, head=lambda hi: hi):
        return pl.BlockSpec((1, tile, d), lambda bi, hi, p, ti: (
            bi, ti + p * (tiles - 1 - 2 * ti), head(hi)))

    def down(d):
        return pl.BlockSpec((1, tile, d), lambda bi, hi, p, ti: (
            bi, tiles - 1 - p * ti, hi))

    dk_, dv_ = heads * dk, heads * dv

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    dq, dk, dv, dla, dbeta = pl.pallas_call(
        functools.partial(_backward_kernel, c=c, s=s, heads=heads),
        grid=(b, h // heads, 2, tiles),
        in_specs=[walked(dk_), walked(dk_), walked(dv_), walked(dk_),
                  walked(h, lambda hi: 0), down(dv_)],
        out_specs=[down(dk_), down(dk_), down(dv_), down(dk_),
                   pl.BlockSpec((1, heads, tile // c, c),
                                lambda bi, hi, p, ti: (
                                    bi, hi, tiles - 1 - p * ti, 0))],
        out_shape=[sds((b, t, h * dk), q.dtype), sds((b, t, h * dk), k.dtype),
                   sds((b, t, h * dv), v.dtype), sds((b, t, h * dk), F32),
                   sds((b, h, t // c, c), F32)],
        scratch_shapes=[
            pltpu.VMEM((heads, dv, dk), F32), pltpu.VMEM((heads, dv, dk), F32),
            pltpu.VMEM((tiles, heads, dv, dk), F32),
            pltpu.VMEM((tiles, chunks, c, c), F32),
            pltpu.VMEM((chunks, c, dv), F32),
            pltpu.VMEM((chunks, c, dk), q.dtype),
            pltpu.VMEM((chunks, c, dk), q.dtype),
            pltpu.VMEM((chunks, 8, dk), F32),
            pltpu.VMEM((chunks, c, dv), F32),
            pltpu.VMEM((chunks, dv, dk), F32),
            pltpu.VMEM((chunks, c, dv), F32),
            pltpu.VMEM((chunks, c, dv), F32),
            pltpu.VMEM((chunks, dv, dk), F32),
            pltpu.VMEM((chunks, dv, dk), F32)],
        compiler_params=_params(
            ("parallel", "parallel", "arbitrary", "arbitrary"),
            vmem_bytes(t, dk, dv, c)),
        cost_estimate=_cost(q, v, beta, 2, True),
        interpret=interpret,
        name="delta_rule_bwd",
    )(q, k, v, log_alpha, beta, do)
    return dq, dk, dv, dla, jnp.moveaxis(dbeta.reshape(b, h, t), 1, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _channel_rule(q, k, v, log_alpha, beta, c: int, s: int, interpret: bool):
    return _forward_call(q, k, v, log_alpha, beta, c=c, s=s,
                         interpret=interpret)


def _channel_rule_fwd(q, k, v, log_alpha, beta, c, s, interpret):
    # nothing of the forward call is kept: the backward call recomputes the
    # states from the operands
    return (_forward_call(q, k, v, log_alpha, beta, c=c, s=s,
                          interpret=interpret), (q, k, v, log_alpha, beta))


def _channel_rule_bwd(c, s, interpret, operands, do):
    with obs.device_scope(SCOPE):
        return _backward_call(*operands, do, c=c, s=s, interpret=interpret)


_channel_rule.defvjp(_channel_rule_fwd, _channel_rule_bwd)


def channel_gated_delta_rule(q, k, v, log_alpha, beta, chunk: int = CHUNK,
                             sub: int = SUB):
    """``gated_delta_rule`` with A DECAY A CHANNEL: ``log_alpha``
    [b, t, h, dk] (<= 0), float32; the other operands and the result as
    there. Or FLAT, as the two calls read and write them: ``q``, ``k``,
    ``log_alpha`` [b, t, h x dk] and ``v`` [b, t, h x dv], a head's channels
    side by side (``beta`` [b, t, h] says how many heads); ``o`` is then
    [b, t, h x dv] and no ``[t, h, d]`` view is formed anywhere. ``sub``
    must divide the chunk (a chunk shorter than a sub-block is one) a power
    of two times."""
    b, t, h = beta.shape
    flat = q.ndim == 3
    if not flat:
        q, k, v, log_alpha = (x.reshape(b, t, -1)
                              for x in (q, k, v, log_alpha))
    c = min(int(chunk), t)
    s = min(int(sub), c)
    if t % c or c % s or (c // s) & (c // s - 1):
        raise ValueError(f"chunk {c} does not divide the sequence length {t}"
                         f", or sub-block {s} the chunk a power of two times")
    why_not = None if pallas_interpret(None) else _refused(
        t, q.shape[2] // h, v.shape[2] // h, c)
    if why_not:
        raise ValueError(f"channel_gated_delta_rule on a TPU: {why_not}")
    rule = per_shard_under_mesh(
        functools.partial(_channel_rule, c=c, s=s,
                          interpret=pallas_interpret(None)),
        lambda batch: ((P(batch),) * 5, P(batch)))
    with obs.device_scope(SCOPE):
        o = checkpoint_name(
            rule(q, k, v, log_alpha.astype(F32), beta.astype(F32)),
            SAVED_OUTPUT)
        return o if flat else o.reshape(b, t, h, -1)


def _refused(t: int, dk: int, dv: int, c: int):
    """Why the two Mosaic calls cannot take a sequence of ``t`` tokens in
    chunks of ``c`` at heads of ``dk`` x ``dv`` (None: they can): what the
    interpreter bears and the chip does not."""
    if dk % 128 or dv % 128:
        return (f"heads of {dk} x {dv}: a head's channels are a block column "
                "of whole 128-lane tiles")
    if unpartitioned():
        return (f"{jax.device_count()} devices and no mesh: XLA cannot "
                "partition a Mosaic call (trace under jax.set_mesh)")
    ask = vmem_bytes(t, dk, dv, c)
    if ask > VMEM_ASK_BOUND_BYTES:
        return (f"{t} tokens: the backward call keeps every chunk's inverse "
                f"and every grid step's entering state in VMEM, {ask} bytes "
                f"of the {VMEM_ASK_BOUND_BYTES} a call may ask for")
    return None
