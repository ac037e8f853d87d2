"""SmallThinker-21BA3B forward, loss and gradients for ONE CHIP'S SHARE of the
routed experts, written from the published ``config.json``'s keys (the
catalog's row) and the source's modeling file (from memory: no network here),
against the parameter tree ``raydp_tpu.models.HybridLM`` creates for this
family: ``embed`` [V, D], ``head`` [D, V] (untied), ``final_norm``, and
``layer_<i>`` with ``norm1``, ``norm2``, ``wq`` [D, heads x Dh], ``wk``,
``wv`` [D, KV x Dh], ``wo`` [heads x Dh, D], ``router`` [D, E], ``w13``
[held, D, gate | up], ``w2`` [held, F, D]; matrices are [in, out]. Imports
nothing from ``raydp_tpu``.

For layer ``l`` with input ``h``::

    r   = h                                    # the router's input: the block's input
    a   = RMSNorm(h)
    q   = a Wq [heads x Dh];  k = a Wk, v = a Wv [KV x Dh]; K/V head g serves
                                               # query heads g x group ..
    if rope_layout[l]:  q, k = RoPE(q), RoPE(k)           # rotate-half
    keys of query i:  j <= i, and i - j < W if sliding_window_layout[l]
    h   = h + softmax(q k^T / sqrt(Dh)) v Wo
    z   = r Wr [E];  sel = top-k of z;  w = softmax(z[sel])
    h   = h + sum_{e in sel, e held} w_e W2_e(relu(W1_e y) * W3_e y),  y = RMSNorm(h)
    logits = RMSNorm_f(h) W_head

Plain ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernel, no sort. Attention is a full softmax under a dense mask built from
positions, one K/V head's group of query heads and ``QUERY_BLOCK`` queries
at a time (the [T, T] scores of 28 heads at 16,384 tokens are 30 GB whole;
a block of them is [7, 2048, 16384]). The expert layer applies EVERY held
expert to EVERY token under a 0/1 mask. The share (``first_expert``, as many
experts as ``w13`` stacks) is the program's: what the absent experts would
add is left out.

``routing`` (int32 [layers, B, T, k]) takes each layer's selected ids IN
PLACE OF the reference's own top-k; the weights are still from ITS logits at
those ids. ``aux`` always holds the reference's own free ``selection``
[layers, B, T, k] and, per token and layer, the ``margin`` between its k-th
and (k+1)-th logit: where a program's choice differs from the reference's,
that margin says whether rounding explains it (``drivers/
lmpretrain_routed.py``).

``compute_dtype`` (default float32) exists only to produce the benchmark's
second reading: the same reference with every matmul, activation, logit and
the loss in a lower precision.

The optimizer is ``reference/lfm2_moe.py``'s (AdamW in place on the host
under a linear warm-up); this family has no router bias, so ``bias_leaves``
finds none and the balancing rule moves nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.lfm2_moe import (  # noqa: F401 - the driver's
    _rms, _rope, adamw_init, adamw_step, bias_leaves)

QUERY_BLOCK = 2048


def config_of(config: dict) -> dict:
    """What the equations read of a configuration as run."""
    share = config.get("share", {})
    first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
    return {
        "windows": tuple(
            config["sliding_window_size"] if windowed else 0
            for windowed in config["sliding_window_layout"][first:first + depth]),
        "ropes": tuple(bool(r) for r in config["rope_layout"][first:first + depth]),
        "num_attention_heads": config["num_attention_heads"],
        "num_key_value_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["rms_norm_eps"]),
        "num_experts_per_tok": config["moe_num_active_primary_experts"],
        "first_expert": share.get("first_expert", 0),
    }


def _attention(w, x, cfg, window, rope, checkpoint):
    b, t, _ = x.shape
    heads, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    group = heads // kv
    # K/V head g serves query heads g x group .. (g + 1) x group - 1
    q = (x @ w["wq"]).reshape(b, t, kv, group, dh).transpose(2, 0, 3, 1, 4)
    k = (x @ w["wk"]).reshape(b, t, kv, dh).transpose(2, 0, 1, 3)
    v = (x @ w["wv"]).reshape(b, t, kv, dh).transpose(2, 0, 1, 3)
    if rope:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    keys = jnp.arange(t)

    def one_block(k_g, v_g, q_blk, start):
        # q_blk [b, group, block, dh] against every key of its K/V head
        behind = (start + jnp.arange(block))[:, None] - keys[None, :]
        seen = behind >= 0
        if window:
            seen &= behind < window  # the query's own position counts
        scores = jnp.einsum("bgqd,bkd->bgqk", q_blk, k_g) * jnp.asarray(
            dh ** -0.5, x.dtype)
        scores = jnp.where(seen, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bgqd", jax.nn.softmax(scores, axis=-1),
                          v_g)

    if checkpoint:
        one_block = jax.checkpoint(one_block)

    def one_group(qkv):
        q_g, k_g, v_g = qkv  # [b, group, t, dh], [b, t, dh] x 2
        blocks = q_g.reshape(b, group, t // block, block, dh).transpose(
            2, 0, 1, 3, 4)
        starts = jnp.arange(0, t, block)
        out = jax.lax.map(lambda qs: one_block(k_g, v_g, *qs),
                          (blocks, starts))  # [t / block, b, group, block, dh]
        return out.transpose(1, 2, 0, 3, 4).reshape(b, group, t, dh)

    out = jax.lax.map(one_group, (q, k, v))  # [kv, b, group, t, dh]
    return out.transpose(1, 3, 0, 2, 4).reshape(b, t, heads * dh) @ w["wo"]


def _reglu(x, w_in, w_out):
    gu = x @ w_in
    half = gu.shape[-1] // 2
    return (jax.nn.relu(gu[..., :half]) * gu[..., half:]) @ w_out


def _experts(w, r, u, cfg, routing, checkpoint):
    """(the held experts' part of the result for ``u``, routed from ``r``;
    the free selection; the margin). ``routing`` [B, T, k] replaces the
    selection where given."""
    k = cfg["num_experts_per_tok"]
    logits = r @ w["router"]
    top, free = jax.lax.top_k(logits, k + 1)
    margin = (top[..., k - 1] - top[..., k]).astype(jnp.float32)
    free = free[..., :k]
    sel = free if routing is None else routing
    weight = jax.nn.softmax(jnp.take_along_axis(logits, sel, axis=-1), axis=-1)

    def one(w13, w2, e):
        # the weight this expert has in each token's sum: 0 where not chosen
        share = jnp.sum(jnp.where(sel == cfg["first_expert"] + e, weight, 0),
                        axis=-1)
        return share[..., None] * _reglu(u, w13, w2)

    if checkpoint:
        one = jax.checkpoint(one)
    held = w["w13"].shape[0]
    # expert after expert, one sum: a loop keeps one expert's gradients and
    # one running sum alive, sixteen unrolled terms kept sixteen of each
    out, _ = jax.lax.scan(
        lambda total, e: (total + one(e[0], e[1], e[2]), None),
        jnp.zeros_like(u), (w["w13"], w["w2"], jnp.arange(held)))
    return out, free, margin


def _block(w, h, cfg, window, rope, routing, checkpoint):
    eps = cfg["norm_eps"]
    r = h  # the router reads the block's input, before the first norm
    h = h + _attention(w, _rms(h, w["norm1"], eps), cfg, window, rope,
                       checkpoint)
    out, free, margin = _experts(w, r, _rms(h, w["norm2"], eps), cfg, routing,
                                 checkpoint)
    return h + out, (free, margin)


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def hidden(params, tokens, cfg, checkpoint=False, compute_dtype=jnp.float32,
           routing=None):
    """(the final norm's output [B, T, D], the free selection [layers, B, T,
    k], the margins [layers, B, T])."""
    p = _cast(params["params"], compute_dtype)
    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        told = []
        for i, (window, rope) in enumerate(zip(cfg["windows"], cfg["ropes"])):
            def block(w, h, forced, window=window, rope=rope):
                return _block(w, h, cfg, window, rope, forced, checkpoint)

            if checkpoint:
                block = jax.checkpoint(block)
            h, said = block(p[f"layer_{i}"], h,
                            None if routing is None else routing[i])
            told.append(said)
        selection, margin = (jnp.stack(x) for x in zip(*told))
        return _rms(h, p["final_norm"], cfg["norm_eps"]), selection, margin


def logits_of(params, h, cfg, compute_dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        return h @ params["params"]["head"].astype(compute_dtype)


def forward(params, tokens, cfg, routing=None):
    """Logits [B, T, V], whole (small sizes)."""
    return logits_of(params, hidden(params, tokens, cfg, routing=routing)[0],
                     cfg)


def token_losses(params, h, targets, cfg, token_block=0,
                 compute_dtype=jnp.float32):
    """Every token's cross-entropy [B, T] from the final norm's output ``h``
    [B, T, D], in ``compute_dtype`` from the logits to the loss."""
    b, t, d = h.shape

    def ce(h_blk, y_blk):
        z = logits_of(params, h_blk, cfg, compute_dtype)
        z = z - jnp.max(z, axis=-1, keepdims=True)
        log_probs = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
        return -jnp.take_along_axis(log_probs, y_blk[:, None], axis=-1)[:, 0]

    flat_h, flat_y = h.reshape(b * t, d), targets.reshape(b * t)
    if not token_block or token_block >= b * t or (b * t) % token_block:
        return ce(flat_h, flat_y).reshape(b, t)
    # block after block in one loop: the head's gradient is one running sum
    blocks = (b * t) // token_block
    parts = jax.lax.map(
        lambda hy: jax.checkpoint(ce)(*hy),
        (flat_h.reshape(blocks, token_block, d),
         flat_y.reshape(blocks, token_block)))
    return parts.reshape(b, t)


def loss(params, x, cfg, token_block=0, checkpoint=False,
         compute_dtype=jnp.float32, with_states=False, routing=None):
    """(mean next-token cross-entropy, aux) on x int32 [B, T+1]: inputs
    x[:, :-1], targets x[:, 1:]. ``aux``: ``selection``, ``margin`` and,
    ``with_states``, the final norm's output (``hidden``)."""
    h, selection, margin = hidden(params, x[:, :-1], cfg, checkpoint,
                                  compute_dtype, routing)
    ce = token_losses(params, h, x[:, 1:], cfg, token_block, compute_dtype)
    aux = {"selection": selection, "margin": margin}
    if with_states:
        aux["hidden"] = h
    return jnp.mean(ce).astype(jnp.float32), aux


def loss_and_grads(params, x, cfg, token_block=0, checkpoint=False,
                   compute_dtype=jnp.float32, with_states=False, routing=None):
    (value, aux), grads = jax.value_and_grad(
        lambda p: loss(p, x, cfg, token_block, checkpoint, compute_dtype,
                       with_states, routing), has_aux=True)(params)
    return value, aux, grads
