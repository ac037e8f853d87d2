"""Ring attention: exact attention over sequences sharded across devices.

Long-context is first-class in this framework (the reference has no sequence
axis at all — SURVEY.md §5 "long-context: absent"). The sequence is sharded
over a mesh axis; each device holds a Q/K/V block. K/V blocks rotate around
the ring via ``lax.ppermute`` while every device accumulates its Q block's
attention with the numerically-stable online-softmax update (flash-attention
statistics: running max m, denominator l, unnormalized output o). After
``axis_size`` steps every Q block has attended to the full sequence — exact
attention, O(T/N) memory per device, and the permute overlaps with compute
under XLA's latency-hiding scheduler on ICI.

Causal masking uses global block offsets derived from ``lax.axis_index``:
a rotated K/V block j contributes fully when j < i, triangularly when j == i,
and not at all when j > i (those steps still run — uniform control flow — but
are masked to -inf so the softmax ignores them).
"""

from __future__ import annotations

import functools
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _block_attn(q, k, v, mask):
    """Scores for one (Q-block, K-block) pair + masked online-softmax stats.

    q: [B, H, Tq, D], k/v: [B, H, Tk, D], mask: [Tq, Tk] bool (True = keep).
    Returns (o_un, m, l): unnormalized output, row max, row denom.
    """
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    m = jnp.max(scores, axis=-1)  # [B,H,Tq]
    # guard all-masked rows: exp(NEG_INF - NEG_INF) would be 1, so zero them
    row_valid = jnp.any(mask, axis=-1)[None, None]  # [1,1,Tq broadcast]
    p = jnp.exp(scores - m[..., None])
    p = jnp.where(mask[None, None], p, 0.0)
    l = jnp.sum(p, axis=-1)  # noqa: E741 - flash-attention notation
    o = jnp.einsum("bhqk,bhkd->bhqd", p, v)
    m = jnp.where(row_valid, m, NEG_INF)
    return o, m, l


def _merge(o1, m1, l1, o2, m2, l2):
    """Combine two online-softmax partials (standard flash merge)."""
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    o = o1 * a1[..., None] + o2 * a2[..., None]
    l = l1 * a1 + l2 * a2  # noqa: E741
    return o, m, l


def _ring_forward_stats(q, k, v, axis_name, causal, use_flash):
    """Ring forward returning (o_unnormalized, m, l)."""
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    b, h, t, d = q.shape
    tk = k.shape[2]

    q_pos = jnp.arange(t)
    k_pos = jnp.arange(tk)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def block_product(k_cur, v_cur, s):
        """(o_un, m, l) of q against the K/V block that originated on device
        (my_idx - s) mod n."""
        src = (my_idx - s) % n
        if use_flash:
            from raydp_tpu.ops.flash_attention import flash_attention_stats

            return flash_attention_stats(
                q, k_cur, v_cur, my_idx * t, src * tk, causal
            )
        if causal:
            gq = my_idx * t + q_pos
            gk = src * tk + k_pos
            mask = gq[:, None] >= gk[None, :]
        else:
            mask = jnp.ones((t, tk), bool)
        return _block_attn(q, k_cur, v_cur, mask)

    # step 0: the local block, no communication
    o, m, l = block_product(k, v, 0)  # noqa: E741

    def step(carry, s):
        o, m, l, k_cur, v_cur = carry  # noqa: E741
        # permute FIRST, then attend — no dead rotation after the last use
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        o2, m2, l2 = block_product(k_cur, v_cur, s)
        o, m, l = _merge(o, m, l, o2, m2, l2)  # noqa: E741
        return (o, m, l, k_cur, v_cur), None

    if n > 1:
        (o, m, l, _, _), _ = lax.scan(  # noqa: E741
            step, (o, m, l, k, v), jnp.arange(1, n)
        )
    return o, m, l


def _block_grads(q, k, v, lse, dsum, g, q_off, k_off, causal, use_flash):
    """(dq, dk, dv) partials of the local Q block against ONE K/V block,
    from the GLOBAL logsumexp/dsum — the backward counterpart of the
    forward's block products."""
    if use_flash:
        from raydp_tpu.ops.flash_attention import flash_backward_blocks

        return flash_backward_blocks(
            q, k, v, lse, dsum, g, q_off, k_off, causal
        )
    scale = q.shape[-1] ** -0.5
    t, tk = q.shape[2], k.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        gq = q_off + jnp.arange(t)
        gk = k_off + jnp.arange(tk)
        s = jnp.where(gq[:, None] >= gk[None, :], s, NEG_INF)
    p = jnp.exp(s - lse[..., None])  # masked rows underflow to exactly 0
    gf = g.astype(jnp.float32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v.astype(jnp.float32))
    ds = p * (dp - dsum[..., None]) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(jnp.float32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(jnp.float32))
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
    use_flash: bool = False,
) -> jnp.ndarray:
    """Exact attention with sequence sharded over ``axis_name``.

    Call inside ``shard_map`` (or any SPMD context where ``axis_name`` is
    bound). Shapes are per-device: q, k, v: [B, H, T_local, D]; the global
    sequence is ``T_local * axis_size`` in ring order.

    ``use_flash=True`` computes each (Q-block, K/V-block) product with the
    fused pallas flash kernel (O(T_local) VMEM, MXU scores) instead of the
    einsum path; the cross-device merge is identical.

    TRAINING is O(T_local) memory either way: the custom VJP runs a second
    ring pass — dk/dv accumulators rotate WITH their K/V blocks and arrive
    home after a full cycle — rebuilding each block's probabilities from the
    saved global logsumexp instead of saving any [T, T] intermediate.
    """
    o, m, l = _ring_forward_stats(q, k, v, axis_name, causal, use_flash)  # noqa: E741
    return (o / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)


def _ring_fwd(q, k, v, axis_name, causal, use_flash):
    o, m, l = _ring_forward_stats(q, k, v, axis_name, causal, use_flash)  # noqa: E741
    out = (o / jnp.maximum(l[..., None], 1e-30)).astype(q.dtype)
    lse = m + jnp.log(jnp.maximum(l, 1e-30))  # [B,H,T] global logsumexp
    return out, (q, k, v, out, lse)


def _ring_bwd(axis_name, causal, use_flash, residuals, g):
    q, k, v, out, lse = residuals
    n = lax.axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    t, tk = q.shape[2], k.shape[2]
    perm = [(i, (i + 1) % n) for i in range(n)]
    dsum = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [B,H,T]

    def step(carry, s):
        dq, k_cur, v_cur, dk_cur, dv_cur = carry
        src = (my_idx - s) % n  # origin of the block currently held
        dq_p, dk_p, dv_p = _block_grads(
            q, k_cur, v_cur, lse, dsum, g,
            my_idx * t, src * tk, causal, use_flash,
        )
        dq = dq + dq_p
        dk_cur = dk_cur + dk_p
        dv_cur = dv_cur + dv_p
        # rotate the block AND its gradient accumulators together: after a
        # full cycle every (k, v, dk, dv) quadruple is back on its home
        # device with contributions from every Q shard accumulated
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        dk_cur = lax.ppermute(dk_cur, axis_name, perm)
        dv_cur = lax.ppermute(dv_cur, axis_name, perm)
        return (dq, k_cur, v_cur, dk_cur, dv_cur), None

    # derive zeros from the inputs so they carry the same varying-axes type
    # under shard_map (a fresh constant is unvaried; the loop body's outputs
    # are varying, and scan requires carry types to match exactly)
    zeros_q = (q * 0).astype(jnp.float32)
    zeros_k = (k * 0).astype(jnp.float32)
    zeros_v = (v * 0).astype(jnp.float32)
    init = (zeros_q, k, v, zeros_k, zeros_v)
    (dq, _, _, dk, dv), _ = lax.scan(step, init, jnp.arange(n))
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


ring_attention.defvjp(_ring_fwd, _ring_bwd)


def ring_attention_sharded(
    q, k, v, mesh, axis: str = "sp", causal: bool = False, use_flash: bool = False
):
    """Convenience wrapper: q/k/v are global arrays sharded over ``axis`` on
    the sequence dim; runs ring_attention under shard_map."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, None, axis, None)

    # use_flash: the pallas interpreter can't reconcile invariant grid slices
    # with varying operands; JAX's documented workaround is check_vma=False
    # (numerics are validated against full attention in tests)
    fn = jax.shard_map(
        partial(
            ring_attention, axis_name=axis, causal=causal, use_flash=use_flash
        ),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not use_flash,
    )
    return fn(q, k, v)


def ulysses_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    axis_name: str,
    causal: bool = False,
    use_flash: bool = False,
) -> jnp.ndarray:
    """DeepSpeed-Ulysses-style sequence parallelism: all-to-all swaps the
    sharded dim from sequence to heads, attention runs locally on full
    sequences for H/N heads, then all-to-all swaps back. Cheaper than a ring
    when H divides the axis and the full sequence fits one device's memory
    budget; call inside shard_map. Per-device shapes: [B, H, T_local, D].
    ``use_flash``: compute the local attention with the fused pallas flash
    kernel (O(T) memory for the gathered sequence) instead of the einsum."""
    n = lax.axis_size(axis_name)
    b, h, t, d = q.shape
    if h % n:
        raise ValueError(f"heads {h} not divisible by sequence axis {n}")

    def seq_to_heads(x):
        # [B, H, T_local, D] -> [B, H/N, T_global, D]
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def heads_to_seq(x):
        # [B, H/N, T_global, D] -> [B, H, T_local, D]
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    if use_flash:
        from raydp_tpu.ops.flash_attention import flash_attention

        # default blocks = pick_blocks: the measured-fastest large tiles
        og = flash_attention(qg, kg, vg, causal)
        return heads_to_seq(og)
    tg = qg.shape[2]
    scale = d**-0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", qg, kg) * scale
    if causal:
        pos = jnp.arange(tg)
        scores = jnp.where(pos[:, None] >= pos[None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    og = jnp.einsum("bhqk,bhkd->bhqd", probs, vg)
    return heads_to_seq(og)


def full_attention(q, k, v, causal: bool = False,
                   window: int | None = None) -> jnp.ndarray:
    """Single-device reference implementation (for tests and small models).
    ``window`` (causal only): a query sees its own position and the
    ``window - 1`` before it."""
    scale = q.shape[-1] ** -0.5
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    if window is not None and not causal:
        raise ValueError("a window is built for causal attention only")
    if causal:
        tq, tk = scores.shape[-2:]
        behind = jnp.arange(tq)[:, None] - jnp.arange(tk)[None, :]
        mask = behind >= 0
        if window is not None:
            mask &= behind < window
        scores = jnp.where(mask, scores, NEG_INF)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, -1), v)
