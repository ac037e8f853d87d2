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

The whole region runs under the device scope ``delta_rule``
(``obs.device_scope``) and its result carries the ``checkpoint_name``
``SAVED_OUTPUT``, so that a trace reader and a save-by-name
``jax.checkpoint`` policy can find it.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from raydp_tpu import obs

SCOPE = "delta_rule"
SAVED_OUTPUT = "delta_out"
# tokens a chunk: the triangular solve is C x C a head and chunk, the serial
# recurrence T / C steps long. 64 is the usual one
CHUNK = 64


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
