"""The Mamba-2 recurrence in its chunked ("state-space dual") form
(arXiv:2405.21060), forward; the backward pass is autodiff's.

Per head, with a state ``S`` [P, N], decay ``a_t = exp(A dt_t)`` (``A < 0``)
and one group of ``B_t``, ``C_t`` [N] shared by the heads::

    S_t = a_t S_{t-1} + dt_t x_t (x) B_t        S_0 = 0
    y_t = S_t C_t + D x_t

A chunk of ``Q`` tokens is worked as matmuls; only the chunk states go
through a serial recurrence, ``T / Q`` steps long::

    cum_i   = sum_{k <= i} log a_k                      inside the chunk
    L[i, j] = exp(cum_i - cum_j)   for j <= i, else 0   the masked decays
    Y_diag  = (L o C B^T) (dt x)                        inside the chunk
    S_c     = B^T (exp(cum_Q - cum) dt x)               what the chunk adds
    S_in    = recurrence over the chunk states          what enters a chunk
    Y_off   = exp(cum) o (C S_in)

``L`` is built from DIFFERENCES of the log-decays, never as a quotient of
cumulative products: with the published initialisation one chunk's
``sum log a`` reaches -400, where ``exp(cum)`` is 0 in float32 and the
quotient 0 / 0. Decays, their sums and the state are float32; the matmuls'
operands are ``x``'s dtype (bf16 on the chip) with float32 accumulation.

The whole region runs under the device scope ``ssd`` (``obs.device_scope``)
and its result carries the ``checkpoint_name`` ``SAVED_OUTPUT``, so that a
trace reader and a save-by-name ``jax.checkpoint`` policy can find it.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from raydp_tpu import obs

SCOPE = "ssd"
SAVED_OUTPUT = "ssd_out"


def ssd_chunk_scan(x, dt, A, B, C, D, chunk: int):
    """``x`` [b, t, h, p]; ``dt`` [b, t, h] (positive: after the softplus);
    ``A`` [h] (negative); ``B``, ``C`` [b, t, n]; ``D`` [h]. Returns ``y``
    [b, t, h, p] in ``x``'s dtype. ``chunk`` must divide ``t`` (a sequence
    shorter than a chunk is one chunk)."""
    b, t, h, p = x.shape
    n = B.shape[-1]
    q = min(int(chunk), t)
    if t % q:
        raise ValueError(f"chunk {q} does not divide the sequence length {t}")
    c = t // q
    dtype, f32 = x.dtype, jnp.float32
    with obs.device_scope(SCOPE):
        dt = dt.astype(f32)
        # head-major inside a chunk: [b, c, h, q]
        log_a = (dt * A.astype(f32)).reshape(b, c, q, h).transpose(0, 1, 3, 2)
        cum = jnp.cumsum(log_a, axis=-1)
        xh = x.reshape(b, c, q, h, p).transpose(0, 1, 3, 2, 4)  # [b,c,h,q,p]
        xd = xh.astype(f32) * dt.reshape(b, c, q, h).transpose(0, 1, 3, 2)[..., None]
        Bc, Cc = B.reshape(b, c, q, n), C.reshape(b, c, q, n)

        # inside a chunk: token j reaches token i >= j through a_{j+1..i}
        keep = jnp.tril(jnp.ones((q, q), bool))
        decays = jnp.exp(jnp.where(
            keep, cum[..., :, None] - cum[..., None, :], -jnp.inf))
        scores = jnp.einsum("bcin,bcjn->bcij", Cc, Bc,
                            preferred_element_type=f32)
        y = jnp.einsum("bchij,bchjp->bchip",
                       (scores[:, :, None] * decays).astype(dtype),
                       xd.astype(dtype), preferred_element_type=f32)

        # what each chunk adds to the state, decayed to the chunk's end
        to_end = jnp.exp(cum[..., -1:] - cum)
        added = jnp.einsum("bcjn,bchjp->bchpn", Bc,
                           (xd * to_end[..., None]).astype(dtype),
                           preferred_element_type=f32)

        # the state that ENTERS each chunk: the one serial part
        def carry_on(state, chunk_in):
            add, decay = chunk_in
            return decay[..., None, None] * state + add, state

        _, entering = lax.scan(
            carry_on, jnp.zeros((b, h, p, n), f32),
            (added.transpose(1, 0, 2, 3, 4),
             jnp.exp(cum[..., -1]).transpose(1, 0, 2)))
        entering = entering.transpose(1, 0, 2, 3, 4)  # [b, c, h, p, n]
        y = y + jnp.exp(cum)[..., None] * jnp.einsum(
            "bcin,bchpn->bchip", Cc, entering.astype(dtype),
            preferred_element_type=f32)

        y = y + D.astype(f32)[:, None, None] * xh.astype(f32)
        y = y.transpose(0, 1, 3, 2, 4).reshape(b, t, h, p).astype(dtype)
        return checkpoint_name(y, SAVED_OUTPUT)
