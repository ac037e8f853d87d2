"""Ling-3.0-flash (``bailing_hybrid``) forward, loss and gradients for ONE
CHIP'S SHARE of the routed experts, written from the published
``config.json``'s keys (the catalog's row), Kimi Delta Attention
(arXiv:2510.26692) and DeepSeek-V2's latent attention (arXiv:2405.04434), from
memory: no network here. Against the parameter tree
``raydp_tpu.models.HybridLM`` creates for this family: ``embed`` [V, D],
``head`` [D, V] (untied), ``final_norm``, and ``layer_<i>`` with ``norm1``,
``norm2``; by the layer's mixer KDA (``wq``, ``wk``, ``wv``, ``wf`` [D, H Dk],
``wb``, ``wg`` [D, H], ``conv_w`` [K, q | k | v channels], ``A_log`` [H],
``dt_bias`` [H Dk], ``gate_norm`` [Dv], ``wo``) or MLA (``wq`` [D, H (nope +
rope)], ``wkva`` [D, rank + rope], ``kv_norm`` [rank], ``wkvb`` [rank, H (nope
+ Dv)], ``wg`` [D, H], ``wo`` [H Dv, D]); by its FFN a dense SwiGLU (``w_in``
[D, gate | up], ``w_out``) or routed experts (``router`` [D, E],
``expert_bias`` [E], ``w13`` [held, D, gate | up], ``w2`` [held, F, D]) beside
a shared one (``shared_in`` [D, gate | up], ``shared_out``); matrices are
[in, out]. Imports nothing from ``raydp_tpu``.

Pre-norm blocks, ``h += Mixer(RMSNorm(h))``, ``h += FFN(RMSNorm(h))``, no
bias; logits from the final norm through the untied head.

KDA on the normed stream ``x`` [T, D], a head at a time (Dk = Dv)::

    q^, k^, v^ = silu(conv(W_q x)), silu(conv(W_k x)), silu(conv(W_v x))
    q_t = l2norm(q^_t) / sqrt(Dk);  k_t = l2norm(k^_t);  v_t = v^_t
    log a_t = kda_lower_bound sigmoid(exp(A_log) ((W_f x)_t + dt_bias))  [Dk]
    beta_t  = sigmoid(W_beta x)_t
    S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T;  S_0 = 0
    o_t = S_t^T q_t
    y_t = W_o [ RMSNorm_head(o_t; gate_norm) sigmoid((W_g x)_t)[head] ]

(held here transposed, [Dv, Dk]: decay a column, erase, write, read); the
recurrence PER TOKEN exactly as written, a two-level ``lax.scan``
(``SCAN_BLOCK`` tokens inside, recomputed in the backward pass: a plain
scan's gradient stores a [H, Dv, Dk] state a token, 2.1 MB x 8192 a layer at
the published widths): no chunk, no sub-block, no triangular solve; it shares
no algebra with ``ops/delta_rule.py``. No positions in a KDA layer.

MLA, a head at a time::

    [q_nope | q_rope] = W_q x;  [c | k_rope] = W_kva x
    [k_nope | v] = W_kvb RMSNorm(c; kv_norm)
    q = [q_nope | RoPE(q_rope)];  k = [k_nope | RoPE(k_rope)]   (one k_rope
    for all the heads; RoPE turns the INTERLEAVED pairs (2i, 2i + 1))
    o = softmax_causal(q . k / sqrt(nope + rope)) v
    y = W_o [ o sigmoid(W_g x)[head] ]

Experts, every held expert on every token under a 0/1 mask::

    s = sigmoid(u W_r);  a group's score = the sum of its 2 largest s + b
    the topk_group best of n_group groups are open; sel = top_k(s + b) inside
    w = s[sel] / (sum s[sel] + 1e-20) x routed_scaling_factor
    out = shared(u) + sum_{e held} (sum_k w[., k] [sel[., k] == e]) expert_e(u)

``routing`` (int32 [expert layers, B, T, k]) takes each expert layer's
selected ids IN PLACE OF the reference's own; the weights are still from ITS
scores at those ids. ``aux`` holds the reference's free ``selection`` and,
per token and layer, the ``margin`` by which rounding could have flipped it:
the SMALLER of the k-th against the (k+1)-th biased score inside the open
groups and the last open group's score against the first closed one's (a
flipped group flips up to k choices).

``compute_dtype`` (default float32) exists only to produce the benchmark's
second reading: the same reference with every matmul, activation, decay,
state, score, logit and the loss in a lower precision. The optimizer is
``lfm2_moe``'s (AdamW under a linear warm-up; the balancing rule on the
expert biases) through ``olmo_hybrid``'s blocked in-place AdamW.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference import olmo_hybrid
from benchmark.reference.granite_hybrid import (  # noqa: F401 - the driver's
    _cast, _rms, adamw_init)
from benchmark.reference.lfm2_moe import (  # noqa: F401 - the driver's
    _swiglu, bias_leaves)

SCAN_BLOCK = 64
L2_EPS = 1e-6
WEIGHT_EPS = 1e-20


def config_of(config: dict) -> dict:
    """What the equations read of a configuration as run: the layers built
    are published layers ``first_layer .. first_layer + num_hidden_layers -
    1`` (from 0; layer l is MLA where ``(l + 1) % layer_group_size`` is 0),
    the first ``first_k_dense_replace`` of them dense."""
    share = config.get("share", {})
    first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
    dense = config["first_k_dense_replace"]
    keys = ("num_attention_heads", "head_dim", "short_conv_kernel_size",
            "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok", "n_group", "topk_group")
    return {
        **{k: config[k] for k in keys},
        "layer_types": tuple(
            "mla" if (layer + 1) % config["layer_group_size"] == 0 else "kda"
            for layer in range(first, first + depth)),
        "ffn_types": ("dense",) * dense + ("experts",) * (depth - dense),
        "kda_lower_bound": float(config["kda_lower_bound"]),
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "first_expert": share.get("first_expert", 0),
    }


def _recurrence(q, k, v, alpha, beta, scan_block):
    """The per-token rule. ``q``, ``k``, ``alpha`` [b, t, h, dk]; ``v``
    [b, t, h, dv]; ``beta`` [b, t, h]. Returns o [b, t, h, dv]."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        state = state * a_t[:, :, None, :]  # decay: column d by a_t[d]
        held = jnp.einsum("bhvd,bhd->bhv", state, k_t)  # what k_t reads now
        state = state - (b_t[..., None] * held)[..., None] * k_t[:, :, None, :]
        state = state + (b_t[..., None] * v_t)[..., None] * k_t[:, :, None, :]
        return state, jnp.einsum("bhvd,bhd->bhv", state, q_t)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    inner = scan_block if t % scan_block == 0 else t
    time_major = [z.swapaxes(0, 1).reshape((t // inner, inner) + z.shape[:1]
                                           + z.shape[2:])
                  for z in (q, k, v, alpha, beta)]
    _, o = jax.lax.scan(block, jnp.zeros((b, h, dv, dk), q.dtype), time_major)
    return o.reshape((t, b, h, dv)).swapaxes(0, 1)


def _kda(w, x, cfg):
    b, t, _ = x.shape
    heads, dk = cfg["num_attention_heads"], cfg["head_dim"]
    taps = cfg["short_conv_kernel_size"]

    def conv_silu(z, conv_w):
        # depthwise, causal: tap K - 1 is the current token's
        padded = jnp.pad(z, ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(conv_w[i] * padded[:, i:i + t]
                               for i in range(taps)))

    def l2(z):
        return z / jnp.sqrt(jnp.sum(z * z, axis=-1, keepdims=True)
                            + jnp.asarray(L2_EPS, z.dtype))

    keys = heads * dk
    q = conv_silu(x @ w["wq"], w["conv_w"][:, :keys])
    k = conv_silu(x @ w["wk"], w["conv_w"][:, keys:2 * keys])
    v = conv_silu(x @ w["wv"], w["conv_w"][:, 2 * keys:])
    q = l2(q.reshape(b, t, heads, dk)) * jnp.asarray(dk ** -0.5, x.dtype)
    k = l2(k.reshape(b, t, heads, dk))
    gate = (x @ w["wf"] + w["dt_bias"]).reshape(b, t, heads, dk)
    alpha = jnp.exp(jnp.asarray(cfg["kda_lower_bound"], x.dtype)
                    * jax.nn.sigmoid(jnp.exp(w["A_log"])[:, None] * gate))
    beta = jax.nn.sigmoid(x @ w["wb"])
    o = _recurrence(q, k, v.reshape(b, t, heads, -1), alpha, beta, SCAN_BLOCK)
    o = _rms(o, w["gate_norm"], cfg["rms_norm_eps"])
    o = o * jax.nn.sigmoid(x @ w["wg"])[..., None]
    return o.reshape(b, t, -1) @ w["wo"]


def _rope_pairs(x, theta):
    """x [..., T, d]: the pairs (x[2i], x[2i + 1]) turned by t theta^(-2i/d)."""
    t, d = x.shape[-2], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(angles).astype(x.dtype), jnp.sin(angles).astype(x.dtype)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def _mla(w, x, cfg, checkpoint):
    b, t, _ = x.shape
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    theta = cfg["rope_theta"]
    q = (x @ w["wq"]).reshape(b, t, heads, nope + rope).transpose(2, 0, 1, 3)
    down = x @ w["wkva"]
    latent, k_rope = down[..., :rank], down[..., rank:]
    up = (_rms(latent, w["kv_norm"], cfg["rms_norm_eps"]) @ w["wkvb"]).reshape(
        b, t, heads, nope + dv).transpose(2, 0, 1, 3)
    k_rope = _rope_pairs(k_rope, theta)  # [b, t, rope]: every head's
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = jnp.asarray((nope + rope) ** -0.5, x.dtype)

    def one_head(args):
        q_h, up_h = args  # [b, t, nope + rope], [b, t, nope + dv]
        scores = (jnp.einsum("bqd,bkd->bqk", q_h[..., :nope], up_h[..., :nope])
                  + jnp.einsum("bqd,bkd->bqk",
                               _rope_pairs(q_h[..., nope:], theta), k_rope)
                  ) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1),
                          up_h[..., nope:])

    if checkpoint:
        one_head = jax.checkpoint(one_head)
    o = jax.lax.map(one_head, (q, up)).transpose(1, 2, 0, 3)  # [b, t, h, dv]
    o = o * jax.nn.sigmoid(x @ w["wg"])[..., None]
    return o.reshape(b, t, heads * dv) @ w["wo"]


def _experts(w, u, cfg, routing, checkpoint):
    """(the shared expert's result plus the held experts' part, the free
    selection, the margin, every expert's excess load under the selection
    used). ``routing`` [B, T, k] replaces the selection where given."""
    k = cfg["num_experts_per_tok"]
    groups, kept = cfg["n_group"], cfg["topk_group"]
    total = w["router"].shape[1]
    scores = jax.nn.sigmoid(u @ w["router"])
    biased = scores + w["expert_bias"].astype(u.dtype)
    margin = None
    if groups:
        grouped = biased.reshape(biased.shape[:-1] + (groups, total // groups))
        of_group = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)
        ranked, order = jax.lax.top_k(of_group, min(kept + 1, groups))
        if kept < groups:
            margin = (ranked[..., kept - 1] - ranked[..., kept]).astype(
                jnp.float32)
        is_open = (order[..., :kept, None]
                   == jnp.arange(groups)).any(axis=-2)
        biased = jnp.where(is_open[..., None], grouped, -jnp.inf).reshape(
            biased.shape)
    top, free = jax.lax.top_k(biased, k + 1)
    inside = (top[..., k - 1] - top[..., k]).astype(jnp.float32)
    margin = inside if margin is None else jnp.minimum(inside, margin)
    free = free[..., :k]
    sel = free if routing is None else routing
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    weight = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                       + jnp.asarray(WEIGHT_EPS, u.dtype)) * jnp.asarray(
                           cfg["routed_scaling_factor"], u.dtype)

    def one(w13, w2, share):
        return share[..., None] * _swiglu(u, w13, w2)

    if checkpoint:
        one = jax.checkpoint(one)
    out = _swiglu(u, w["shared_in"], w["shared_out"])
    for e in range(w["w13"].shape[0]):
        # the weight this expert has in each token's sum: 0 where not chosen
        share = jnp.sum(jnp.where(sel == cfg["first_expert"] + e, weight, 0),
                        axis=-1)
        out = out + one(w["w13"][e], w["w2"][e], share)
    chosen = jnp.sum(sel[..., None] == jnp.arange(total), axis=(0, 1, 2))
    excess = chosen.astype(jnp.float32) / (sel.size / total) - 1.0
    return out, free, margin, excess


def _block(kind, ffn, w, h, cfg, routing, checkpoint):
    eps = cfg["rms_norm_eps"]
    y = _rms(h, w["norm1"], eps)
    h = h + (_kda(w, y, cfg) if kind == "kda" else _mla(w, y, cfg, checkpoint))
    y = _rms(h, w["norm2"], eps)
    if ffn == "dense":
        return h + _swiglu(y, w["w_in"], w["w_out"]), None
    out, *said = _experts(w, y, cfg, routing, checkpoint)
    return h + out, tuple(said)


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
        return (_rms(h, p["final_norm"], cfg["rms_norm_eps"]), selection,
                margin, excess)


def logits_of(params, h, cfg, compute_dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        return h @ params["params"]["head"].astype(compute_dtype)


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


def adamw_step(leaves, grads, state, learning_rate, b1, b2, weight_decay,
               warmup_steps=0, expert_bias_rate=0.0, biases=()):
    """One step IN PLACE, ``lfm2_moe.adamw_step``'s rule (the leaves
    ``biases`` take ``b -= expert_bias_rate x grads`` and leave AdamW; the
    others AdamW at ``learning_rate x min(1, step / warmup_steps)``, steps
    from 1) through ``olmo_hybrid``'s AdamW, which works a leaf in blocks of
    rows (the head is 201 MB here)."""
    step = state["count"] + 1
    rate = learning_rate * min(1.0, step / warmup_steps) if (
        warmup_steps) else learning_rate
    grads = list(grads)
    for i in biases if expert_bias_rate else ():
        leaves[i] -= np.float32(expert_bias_rate) * grads[i]
        grads[i] = np.zeros_like(grads[i])
    return olmo_hybrid.adamw_step(leaves, grads, state, rate, b1, b2,
                                  weight_decay)
