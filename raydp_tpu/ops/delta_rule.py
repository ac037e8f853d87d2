"""The gated delta rule (Gated DeltaNet, arXiv:2412.06464) in its chunked
form, forward; the backward pass is autodiff's.

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

A chunk is products and ONE unit-lower-triangular solve of size ``C``; the
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
is cut into SUB-BLOCKS of ``SUB`` tokens: inside one the decayed products are
formed directly from ``G_i - G_j`` (one head at a time: [.., SUB, SUB, Dk] of
all the heads at once is 2.1 GB a layer at 8192 tokens x 32 heads of 128),
and between a sub-block and an earlier one through the later one's FIRST ROW
a: ``exp(G_i - G_a) exp(G_a - G_j)``, both exponents <= 0 (the rule above).

The whole region runs under the device scope ``delta_rule``
(``obs.device_scope``), whichever entry point, and its result carries the
``checkpoint_name`` ``SAVED_OUTPUT``, so that a trace reader and a
save-by-name ``jax.checkpoint`` policy can find it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from raydp_tpu import obs

SCOPE = "delta_rule"
SAVED_OUTPUT = "delta_out"
# tokens a chunk: the triangular solve is C x C a head and chunk, the serial
# recurrence T / C steps long. 64 is the usual one
CHUNK = 64
# tokens a sub-block of the channel-decay form: inside one, a decayed product
# is [SUB, SUB, Dk] elementwise work; between two, a matrix product
SUB = 16
# heads the channel-decay form works at a time: each group's pass is
# recomputed in ITS backward pass, so what autodiff holds of a layer's scan
# (a dozen float32 [T, H, Dk] arrays and a state a chunk: 3.8 GB a layer at
# 8192 tokens x 32 heads of 128) is a group's. The serial recurrence over the
# chunk states runs once a group
HEADS_AT_ONCE = 8


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


def _within_sub_blocks(q, k, g):
    """The decayed products INSIDE each sub-block, from ``G_i - G_j``
    directly: ``q``, ``k``, ``g`` float32 [h, ..., s, dk] ->
    (``qk``, ``kk``) [h, ..., s, s] with ``[i, j] = sum_d x_i[d] k_j[d]
    exp(g_i[d] - g_j[d])`` for j <= i and 0 above the diagonal. One head at
    a time, recomputed in the backward pass: [.., s, s, dk] is never held
    for more than a head."""
    s = q.shape[-2]
    keep = jnp.tril(jnp.ones((s, s), bool))[..., None]

    @jax.checkpoint
    def head(x):
        q_h, k_h, g_h = x
        decayed = k_h[..., None, :, :] * jnp.exp(jnp.where(
            keep, g_h[..., :, None, :] - g_h[..., None, :, :], -jnp.inf))
        return (jnp.sum(q_h[..., :, None, :] * decayed, axis=-1),
                jnp.sum(k_h[..., :, None, :] * decayed, axis=-1))

    return lax.map(head, (q, k, g))


def _decayed_scores(q, k, g, sub: int, dtype):
    """(``QK``, ``KK``) float32 [..., h, c, c] of a chunk whose decay is a
    vector: ``q``, ``k`` and the running log-decay sums ``g`` are float32
    [..., h, c, dk]; lower triangular, the diagonal in. A sub-block against
    itself: ``_within_sub_blocks``. Against the sub-blocks before it: one
    product, its rows decayed from the sub-block's first row ``a`` on and
    the earlier keys up to ``a`` (operands in ``dtype``)."""
    c, dk = q.shape[-2:]
    m = c // sub
    lead = q.shape[:-2]

    def blocks(x):  # [..., h, c, dk] -> [h, ..., m, sub, dk]
        return jnp.moveaxis(x.reshape(lead + (m, sub, dk)), len(lead) - 1, 0)

    inside = [jnp.moveaxis(x, 0, len(lead) - 1)  # [..., h, m, sub, sub]
              for x in _within_sub_blocks(blocks(q), blocks(k), blocks(g))]
    rows = ([], [])
    for i in range(m):
        here = slice(i * sub, (i + 1) * sub)
        parts = [[x[..., i, :, :]] for x in inside]
        if i:
            first = g[..., i * sub:i * sub + 1, :]
            since = jnp.exp(g[..., here, :] - first)
            until = (k[..., :i * sub, :] * jnp.exp(
                first - g[..., :i * sub, :])).astype(dtype)
            both = jnp.concatenate(
                [q[..., here, :] * since, k[..., here, :] * since],
                axis=-2).astype(dtype)
            before = jnp.einsum("...id,...jd->...ij", both, until,
                                preferred_element_type=jnp.float32)
            parts = [[before[..., :sub, :]] + parts[0],
                     [before[..., sub:, :]] + parts[1]]
        after = jnp.zeros(lead + (sub, c - (i + 1) * sub), jnp.float32)
        for row, part in zip(rows, parts):
            row.append(jnp.concatenate(part + [after], axis=-1))
    return tuple(jnp.concatenate(row, axis=-2) for row in rows)


def channel_gated_delta_rule(q, k, v, log_alpha, beta, chunk: int = CHUNK,
                             sub: int = SUB,
                             heads_at_once: int = HEADS_AT_ONCE):
    """``gated_delta_rule`` with A DECAY A CHANNEL: ``log_alpha``
    [b, t, h, dk] (<= 0), float32; the other operands and the result as
    there. ``sub`` must divide the chunk (a chunk shorter than a sub-block
    is one). More than ``heads_at_once`` heads go in groups of so many (it
    must divide them), one after the other, each group's pass recomputed
    in its own backward pass: a caller whose recomputed block KEEPS
    ``SAVED_OUTPUT`` runs the scan twice a step, not three times."""
    b, t, h, dk = q.shape
    c = min(int(chunk), t)
    s = min(int(sub), c)
    if t % c or c % s:
        raise ValueError(f"chunk {c} does not divide the sequence length {t}"
                         f", or sub-block {s} the chunk")
    operands = (q, k, v, log_alpha.astype(jnp.float32),
                beta.astype(jnp.float32))
    with obs.device_scope(SCOPE):
        if h <= heads_at_once:
            o = _channel_rule(*operands, c, s)
        else:
            if h % heads_at_once:
                raise ValueError(f"{h} heads do not go in groups of "
                                 f"{heads_at_once}")

            def grouped(x):  # [b, t, h, ...] -> [groups, b, t, heads, ...]
                return jnp.moveaxis(x.reshape(
                    (b, t, h // heads_at_once, heads_at_once) + x.shape[3:]),
                    2, 0)

            group = jax.checkpoint(lambda args: _channel_rule(*args, c, s))
            o = lax.map(group, tuple(grouped(x) for x in operands))
            o = jnp.moveaxis(o, 0, 2).reshape(b, t, h, o.shape[-1])
        return checkpoint_name(o, SAVED_OUTPUT)


def _channel_rule(q, k, v, log_alpha, beta, c: int, s: int):
    """``channel_gated_delta_rule`` on the heads given (``log_alpha`` and
    ``beta`` float32), in chunks of ``c`` tokens and sub-blocks of ``s``."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]
    n = t // c
    dtype, f32 = q.dtype, jnp.float32

    def chunked(x):  # [b, t, h, ...] -> [b, n, h, c, ...], head-major
        x = x.reshape((b, n, c, h) + x.shape[3:])
        return jnp.moveaxis(x, 3, 2)

    qc, kc, vc = chunked(q), chunked(k), chunked(v)
    kf = kc.astype(f32)
    bc = chunked(beta)
    g = jnp.cumsum(chunked(log_alpha), axis=-2)  # [b, n, h, c, dk]

    qk, kk = _decayed_scores(qc.astype(f32), kf, g, s, dtype)
    strict = jnp.tril(jnp.ones((c, c), bool), -1)
    m = bc[..., None] * jnp.where(strict, kk, 0.0)
    inverse = _unit_lower_inverse(m).astype(dtype)
    u0 = jnp.einsum("bnhij,bnhjd->bnhid", inverse,
                    (bc[..., None] * vc.astype(f32)).astype(dtype),
                    preferred_element_type=f32)
    w = jnp.einsum("bnhij,bnhjd->bnhid", inverse,
                   (bc[..., None] * jnp.exp(g) * kf).astype(dtype),
                   preferred_element_type=f32)
    # a chunk's keys decayed to the chunk's end: what its U writes
    k_end = (jnp.exp(g[..., -1:, :] - g) * kf).astype(dtype)

    # the state that ENTERS each chunk: the one serial part
    def carry_on(state, chunk_in):
        u0_c, w_c, k_c, decay = chunk_in
        u = u0_c - jnp.einsum("bhid,bhvd->bhiv", w_c.astype(dtype),
                              state.astype(dtype),
                              preferred_element_type=f32)
        out = decay[..., None, :] * state + jnp.einsum(
            "bhiv,bhid->bhvd", u.astype(dtype), k_c,
            preferred_element_type=f32)
        return out, (state, u)

    def chunk_major(x):
        return jnp.moveaxis(x, 1, 0)

    _, (entering, u) = lax.scan(
        carry_on, jnp.zeros((b, h, dv, dk), f32),
        (chunk_major(u0), chunk_major(w), chunk_major(k_end),
         chunk_major(jnp.exp(g[..., -1, :]))))
    entering, u = jnp.moveaxis(entering, 0, 1), jnp.moveaxis(u, 0, 1)

    o = jnp.einsum("bnhij,bnhjv->bnhiv", qk.astype(dtype),
                   u.astype(dtype), preferred_element_type=f32)
    o = o + jnp.einsum(
        "bnhid,bnhvd->bnhiv", (jnp.exp(g) * qc.astype(f32)).astype(dtype),
        entering.astype(dtype), preferred_element_type=f32)
    return jnp.moveaxis(o, 2, 3).reshape(b, t, h, dv).astype(dtype)
