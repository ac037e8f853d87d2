"""LFM2-8B-A1B (``lfm2_moe``) forward, loss and gradients for ONE CHIP'S SHARE
of the routed experts, written from the published ``config.json``'s keys and
the source's ``modeling_lfm2_moe.py`` (from memory: no network here), against
the parameter tree ``raydp_tpu.models.HybridLM`` creates for this family:
``embed`` [V, D] (the head is its transpose), ``final_norm``, and
``layer_<i>`` with ``norm1`` (operator_norm), ``norm2`` (ffn_norm); by the
layer's mixer a gated short convolution (``in_proj`` [D, B | C | x],
``conv_w`` [K, D], ``out_proj``) or attention (``wq`` [D, D], ``wk``, ``wv``
[D, KV x Dh], ``wo``, ``q_norm``, ``k_norm`` [Dh]); by its FFN a dense SwiGLU
(``w_in`` [D, gate | up], ``w_out``) or routed experts (``router`` [D, E],
``expert_bias`` [E], ``w13`` [held, D, gate | up], ``w2`` [held, F, D]);
matrices are [in, out]. Imports nothing from ``raydp_tpu``.

Plain ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernel, no sort. The expert layer applies EVERY held expert to EVERY token
under a 0/1 mask::

    s = sigmoid(u W_g);  sel = top_k(s + b);  w = s[sel] / (sum s[sel] + 1e-6) x scaling
    out = sum_{e held} (sum_k w[., k] [sel[., k] == e]) W2_e(silu(W1_e u) W3_e u)

The share (``first_expert``, as many experts as ``w13`` stacks) is the
program's: what the absent experts would add is left out. The convolution
is ``K`` shifted products; attention is a full softmax over all keys, one
K/V head's group of query heads at a time; RoPE is rotate-half over the
whole head, after the per-head q/k norms.

Two things a routed model's comparison needs. ``routing`` (int32 [expert
layers, B, T, k]) takes each expert layer's selected ids IN PLACE OF the
reference's own top-k; the weights are still from ITS scores at those ids.
And ``aux`` always holds the reference's own free ``selection`` [expert
layers, B, T, k] and, per token and layer, the ``margin`` between its k-th
and (k+1)-th biased score: where a program's choice differs from the
reference's, that margin says whether rounding explains it.

``compute_dtype`` (default float32) exists only to produce the benchmark's
second reading: the same reference with every matmul, activation, score,
logit and the loss in a lower precision.

The optimizer: ``reference/granite_hybrid.py``'s AdamW (in place, on the
host; decay on every parameter with two or more axes) at the rate a linear
warm-up gives the step, on every leaf but the expert biases. Those take the
BALANCING RULE's step, ``b_e -= rate x excess_e``: ``excess_e`` is the pairs
that chose expert e (of ALL the router's experts, held here or not) over
the even share, less 1, and ``loss_and_grads`` returns it in the place of
``expert_bias``'s gradient (the bias enters a top-k: it has none of the
loss).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import granite_hybrid
from benchmark.reference.granite_hybrid import adamw_init  # noqa: F401 - the driver's


def config_of(config: dict) -> dict:
    """What the equations read of a configuration as run."""
    share = config.get("share", {})
    first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
    kinds = tuple(config["layer_types"][first:first + depth])
    dense = config["num_dense_layers"]
    return {
        "layer_types": kinds,
        "ffn_types": ("dense",) * dense + ("experts",) * (depth - dense),
        "num_attention_heads": config["num_attention_heads"],
        "num_key_value_heads": config["num_key_value_heads"],
        "conv_L_cache": config["conv_L_cache"],
        "rope_theta": float(config["rope_theta"]),
        "norm_eps": float(config["norm_eps"]),
        "num_experts_per_tok": config["num_experts_per_tok"],
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "first_expert": share.get("first_expert", 0),
    }


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [..., T, Dh]: x cos + rotate_half(x) sin."""
    t, dh = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1).astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1).astype(x.dtype)
    half = dh // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attention(w, x, cfg, checkpoint):
    b, t, d = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, group = d // heads, heads // kv
    eps, theta = cfg["norm_eps"], cfg["rope_theta"]
    # K/V head g serves query heads g x group .. (g + 1) x group - 1
    q = _rms((x @ w["wq"]).reshape(b, t, kv, group, dh), w["q_norm"], eps)
    k = _rms((x @ w["wk"]).reshape(b, t, kv, dh), w["k_norm"], eps)
    q = _rope(q.transpose(2, 0, 3, 1, 4), theta)  # [kv, b, group, t, dh]
    k = _rope(k.transpose(2, 0, 1, 3), theta)  # [kv, b, t, dh]
    v = (x @ w["wv"]).reshape(b, t, kv, dh).transpose(2, 0, 1, 3)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_group(qkv):
        q_g, k_g, v_g = qkv
        scores = jnp.einsum("bgqd,bkd->bgqk", q_g, k_g) * jnp.asarray(
            dh ** -0.5, x.dtype)
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bgqd", jax.nn.softmax(scores, axis=-1),
                          v_g)

    if checkpoint:
        one_group = jax.checkpoint(one_group)
    out = jax.lax.map(one_group, (q, k, v))  # [kv, b, group, t, dh]
    return out.transpose(1, 3, 0, 2, 4).reshape(b, t, d) @ w["wo"]


def _short_conv(w, u, cfg):
    """[B | C | x] = W_in u;  W_out(C * conv(B * x)): depthwise, causal,
    tap K - 1 is the current token's, no bias."""
    k, t = cfg["conv_L_cache"], u.shape[1]
    d = u.shape[-1]
    proj = u @ w["in_proj"]
    bm, cm, x = proj[..., :d], proj[..., d:2 * d], proj[..., 2 * d:]
    padded = jnp.pad(bm * x, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(w["conv_w"][i] * padded[:, i:i + t] for i in range(k))
    return (cm * conv) @ w["out_proj"]


def _swiglu(x, w_in, w_out):
    gu = x @ w_in
    half = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :half]) * gu[..., half:]) @ w_out


def _experts(w, u, cfg, routing, checkpoint):
    """(the held experts' part of the result, the free selection, the
    margin, every expert's excess load under the selection used).
    ``routing`` [B, T, k] replaces the selection where given."""
    k = cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(u @ w["router"])
    top, free = jax.lax.top_k(scores + w["expert_bias"].astype(u.dtype), k + 1)
    margin = (top[..., k - 1] - top[..., k]).astype(jnp.float32)
    free = free[..., :k]
    sel = free if routing is None else routing
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    weight = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                       + jnp.asarray(1e-6, u.dtype)) * jnp.asarray(
                           cfg["routed_scaling_factor"], u.dtype)

    def one(w13, w2, share):
        return share[..., None] * _swiglu(u, w13, w2)

    if checkpoint:
        one = jax.checkpoint(one)
    out = jnp.zeros_like(u)
    for e in range(w["w13"].shape[0]):
        # the weight this expert has in each token's sum: 0 where not chosen
        share = jnp.sum(jnp.where(sel == cfg["first_expert"] + e, weight, 0),
                        axis=-1)
        out = out + one(w["w13"][e], w["w2"][e], share)
    total = w["router"].shape[1]
    chosen = jnp.stack([jnp.sum(sel == e) for e in range(total)])
    excess = chosen.astype(jnp.float32) / (sel.size / total) - 1.0
    return out, free, margin, excess


def _block(kind, ffn, w, h, cfg, routing, checkpoint):
    eps = cfg["norm_eps"]
    y = _rms(h, w["norm1"], eps)
    h = h + (_short_conv(w, y, cfg) if kind == "conv"
             else _attention(w, y, cfg, checkpoint))
    y = _rms(h, w["norm2"], eps)
    if ffn == "dense":
        return h + _swiglu(y, w["w_in"], w["w_out"]), None
    out, *said = _experts(w, y, cfg, routing, checkpoint)
    return h + out, tuple(said)


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def hidden(params, tokens, cfg, checkpoint=False, compute_dtype=jnp.float32,
           routing=None):
    """(the final norm's output [B, T, D], the free selection [expert layers,
    B, T, k], the margins [expert layers, B, T], the excess loads [expert
    layers, E])."""
    p = _cast(params["params"], compute_dtype)
    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        told, layer = [], 0
        for i, (kind, ffn) in enumerate(zip(cfg["layer_types"],
                                            cfg["ffn_types"])):
            forced = None
            if ffn == "experts":
                forced = None if routing is None else routing[layer]
                layer += 1

            def block(w, h, forced, kind=kind, ffn=ffn):
                return _block(kind, ffn, w, h, cfg, forced, checkpoint)

            if checkpoint:
                block = jax.checkpoint(block)
            h, said = block(p[f"layer_{i}"], h, forced)
            if said is not None:
                told.append(said)
        selection, margin, excess = (jnp.stack(x) for x in zip(*told))
        return (_rms(h, p["final_norm"], cfg["norm_eps"]), selection, margin,
                excess)


def logits_of(params, h, cfg, compute_dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        return h @ params["params"]["embed"].astype(compute_dtype).T


def forward(params, tokens, cfg, routing=None):
    """Logits [B, T, V], whole (small sizes)."""
    return logits_of(params, hidden(params, tokens, cfg, routing=routing)[0],
                     cfg)


def _cross_entropy(params, h, targets, cfg, token_block, compute_dtype):
    b, t, d = h.shape

    def ce(h_blk, y_blk):
        z = logits_of(params, h_blk, cfg, compute_dtype)
        z = z - jnp.max(z, axis=-1, keepdims=True)
        log_probs = z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))
        return -jnp.take_along_axis(log_probs, y_blk[:, None], axis=-1)[:, 0]

    flat_h, flat_y = h.reshape(b * t, d), targets.reshape(b * t)
    if not token_block or token_block >= b * t:
        return ce(flat_h, flat_y).reshape(b, t)
    ce = jax.checkpoint(ce)
    parts = [ce(flat_h[s:s + token_block], flat_y[s:s + token_block])
             for s in range(0, b * t, token_block)]
    return jnp.concatenate(parts).reshape(b, t)


def loss(params, x, cfg, token_block=0, checkpoint=False,
         compute_dtype=jnp.float32, with_states=False, routing=None):
    """(mean next-token cross-entropy, aux) on x int32 [B, T+1]: inputs
    x[:, :-1], targets x[:, 1:]. ``aux``: ``selection``, ``margin``,
    ``excess`` and, ``with_states``, the final norm's output (``hidden``)."""
    h, selection, margin, excess = hidden(params, x[:, :-1], cfg, checkpoint,
                                          compute_dtype, routing)
    ce = _cross_entropy(params, h, x[:, 1:], cfg, token_block, compute_dtype)
    aux = {"selection": selection, "margin": margin, "excess": excess}
    if with_states:
        aux["hidden"] = h
    return jnp.mean(ce).astype(jnp.float32), aux


def loss_and_grads(params, x, cfg, token_block=0, checkpoint=False,
                   compute_dtype=jnp.float32, with_states=False, routing=None):
    (value, aux), grads = jax.value_and_grad(
        lambda p: loss(p, x, cfg, token_block, checkpoint, compute_dtype,
                       with_states, routing), has_aux=True)(params)
    # the bias has no gradient of the loss; what the balancing rule reads
    # stands in its place
    layers = [f"layer_{i}" for i, ffn in enumerate(cfg["ffn_types"])
              if ffn == "experts"]
    for name, excess in zip(layers, aux.pop("excess")):
        grads["params"][name]["expert_bias"] = excess
    return value, aux, grads


def bias_leaves(params) -> list:
    """The indices of the ``expert_bias`` leaves among ``params``' leaves."""
    flat = jax.tree_util.tree_leaves_with_path(params)
    return [i for i, (path, _) in enumerate(flat)
            if getattr(path[-1], "key", None) == "expert_bias"]


def adamw_step(leaves, grads, state, learning_rate, b1, b2, weight_decay,
               warmup_steps=0, expert_bias_rate=0.0, biases=()):
    """One step IN PLACE (``granite_hybrid.adamw_step``'s way): the leaves
    ``biases`` take ``b -= expert_bias_rate x grads`` and leave AdamW (a
    zero gradient on zero moments moves nothing there); the others AdamW
    at ``learning_rate x min(1, step / warmup_steps)``, steps from 1."""
    step = state["count"] + 1
    rate = learning_rate * min(1.0, step / warmup_steps) if (
        warmup_steps) else learning_rate
    grads = list(grads)
    for i in biases if expert_bias_rate else ():
        leaves[i] -= np.float32(expert_bias_rate) * grads[i]
        grads[i] = np.zeros_like(grads[i])
    return granite_hybrid.adamw_step(leaves, grads, state, rate, b1, b2,
                                     weight_decay)
