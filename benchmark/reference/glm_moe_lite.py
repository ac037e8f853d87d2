"""GLM-4.7-Flash (``glm4_moe_lite``: DeepSeek-V3's block) forward, loss and
gradients for ONE CHIP'S SHARE of the routed experts, written from the
published ``config.json``'s keys (the catalog's row) and DeepSeek-V3's report
(arXiv:2412.19437: latent attention with a low-rank query, section 2.1.1;
``noaux_tc`` sigmoid routing beside a shared expert, 2.1.2; multi-token
prediction, 2.2), from memory: no network here. Against the parameter tree
``raydp_tpu.models.HybridLM`` creates for this family: ``embed`` [V, D],
``head`` [D, V] (untied), ``final_norm``, ``layer_<i>`` and ``mtp_0``. A layer
holds ``norm1``, ``norm2``; the mixer's ``wqa`` [D, q_rank], ``q_norm``
[q_rank], ``wqb`` [q_rank, H (nope + rope)], ``wkva`` [D, kv_rank + rope],
``kv_norm`` [kv_rank], ``wkvb`` [kv_rank, H (nope + Dv)], ``wo`` [H Dv, D];
and by its FFN a dense SwiGLU (``w_in`` [D, gate | up], ``w_out``) or routed
experts (``router`` [D, E], ``expert_bias`` [E], ``w13`` [held, D, gate | up],
``w2`` [held, F, D]) beside a shared one (``shared_in``, ``shared_out``).
``mtp_0`` is such an expert layer with ``eh_proj`` [2 D, D], ``enorm``,
``hnorm`` and ``final_norm`` beside it. Matrices are [in, out]. Imports
nothing from ``raydp_tpu``.

Layer, no bias anywhere, eps ``rms_norm_eps``::

    h = h + MLA(RMSNorm(h; norm1));  h = h + FFN(RMSNorm(h; norm2))
    h_0 = E[t];  logits = RMSNorm(h_L; final_norm) W_head

MLA on the normed stream ``x`` [T, D], a head at a time::

    c_q = RMSNorm(x W_qa; q_norm);  [q_nope | q_rope] = c_q W_qb       a head
    [c_kv | k_r] = x W_kva;  [k_nope | v] = RMSNorm(c_kv; kv_norm) W_kvb  a head
    q = [q_nope | RoPE(q_rope)];  k = [k_nope | RoPE(k_r)]    (ONE k_r for all
    the heads; RoPE turns the INTERLEAVED pairs (2i, 2i + 1), theta rope_theta)
    o = softmax_causal(q . k / sqrt(nope + rope)) v;  y = [o of every head] W_o

a full [T, T] softmax a head, in blocks of ``QUERY_BLOCK`` queries. No gate, no
per-head norm.

FFN: published layers below ``first_k_dense_replace`` a SwiGLU of
``intermediate_size``; the others, every held expert on every token under a
0/1 mask::

    s = sigmoid(u W_r)                           [E], all E experts
    sel = the num_experts_per_tok largest of s + b   (b: expert_bias; n_group 1)
    w = s[sel] / (sum s[sel] + 1e-20) x routed_scaling_factor
    out = sum_{e held} (sum_k w[., k] [sel[., k] == e]) SwiGLU_e(u) + SwiGLU_shared(u)

Multi-token prediction, depth 1, on a row ``t_0 .. t_T`` (inputs ``t_0 ..
t_{T-1}``, so ``h_L`` has T rows)::

    u_i = [ RMSNorm(E[t_{i+1}]; enorm) ; RMSNorm(h_L,i; hnorm) ] W_eh
    g = Block_mtp(u)            (one MLA-over-experts layer, positions 0 .. T-1)
    logits'_i = RMSNorm(g_i; mtp final_norm) W_head
    L = mean_i CE(logits_i, t_{i+1}) + lambda x mean_{i <= T-2} CE(logits'_i, t_{i+2})

``h_L`` is what the final norm READS; ``E`` and ``W_head`` are the main
model's. ``lambda`` is ``cfg["mtp_weight"]``.

``routing`` (int32 [expert layers, B, T, k], the module's block LAST) takes
each expert layer's selected ids IN PLACE OF the reference's own; the weights
are still from ITS scores at those ids. ``aux`` holds the reference's free
``selection`` and, per token and layer, the ``margin`` between its k-th and
(k+1)-th biased score.

``compute_dtype`` (default float32) exists only to produce the benchmark's
second reading: the same reference with every matmul, activation, score,
logit and the loss in a lower precision. The optimizer is ``lfm2_moe``'s
(AdamW under a linear warm-up; the balancing rule on the expert biases)
through ``olmo_hybrid``'s blocked in-place AdamW.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.granite_hybrid import (  # noqa: F401 - the driver's
    _cast, _rms, adamw_init)
from benchmark.reference.lfm2_moe import (  # noqa: F401 - the driver's
    _swiglu, bias_leaves)
# the untied head's product, the cross-entropy of a block of tokens' whole
# logits and the optimizer's step are ``ling_hybrid``'s, letter for letter
from benchmark.reference.ling_hybrid import (  # noqa: F401 - the driver's
    _cross_entropy, adamw_step, logits_of)

QUERY_BLOCK = 1024
WEIGHT_EPS = 1e-20
# every token's cross-entropy [B, T] from the state a head reads, in
# ``compute_dtype`` from the logits to the loss: (params, h [B, T, D],
# targets [B, T], cfg, token_block, compute_dtype), for a comparison token by
# token (``drivers/lmpretrain_routed_placed.py``'s ``token_loss_rms``)
token_losses = _cross_entropy


def config_of(config: dict) -> dict:
    """What the equations read of a configuration as run: the layers built
    are published layers ``first_layer .. first_layer + num_hidden_layers -
    1`` (from 0), dense below ``first_k_dense_replace``; ``mtp_weight`` is
    ``model.kwargs``' (``config.json`` has no key for lambda: a configuration
    as run states it there), and 0 where it states none or has no module."""
    share = config.get("share", {})
    first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "v_head_dim", "num_experts_per_tok")
    modules = config.get("num_nextn_predict_layers", 0)
    weight = config.get("model", {}).get("kwargs", {}).get("mtp_weight", 0.0)
    return {
        **{k: config[k] for k in keys},
        "ffn_types": tuple(
            "dense" if layer < config["first_k_dense_replace"] else "experts"
            for layer in range(first, first + depth)),
        "rope_theta": float(config["rope_theta"]),
        "rms_norm_eps": float(config["rms_norm_eps"]),
        "routed_scaling_factor": float(config["routed_scaling_factor"]),
        "first_expert": share.get("first_expert", 0),
        "mtp_weight": float(weight) if modules else 0.0,
    }


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
    heads = cfg["num_attention_heads"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    theta, eps = cfg["rope_theta"], cfg["rms_norm_eps"]
    rank = w["kv_norm"].shape[0]
    c_q = _rms(x @ w["wqa"], w["q_norm"], eps)
    q = (c_q @ w["wqb"]).reshape(b, t, heads, nope + rope).transpose(2, 0, 1, 3)
    down = x @ w["wkva"]
    c_kv, k_r = down[..., :rank], down[..., rank:]
    up = (_rms(c_kv, w["kv_norm"], eps) @ w["wkvb"]).reshape(
        b, t, heads, nope + dv).transpose(2, 0, 1, 3)
    k_r = _rope_pairs(k_r, theta)  # [b, t, rope]: every head's
    scale = jnp.asarray((nope + rope) ** -0.5, x.dtype)
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t
    position = jnp.arange(t)

    def one_head(args):
        q_h, up_h = args  # [b, t, nope + rope], [b, t, nope + dv]
        q_r = _rope_pairs(q_h[..., nope:], theta)
        k_nope, v = up_h[..., :nope], up_h[..., nope:]

        def queries(start):
            rows = jax.lax.dynamic_slice_in_dim(position, start, block)
            q_n = jax.lax.dynamic_slice_in_dim(q_h[..., :nope], start, block, 1)
            q_p = jax.lax.dynamic_slice_in_dim(q_r, start, block, 1)
            scores = (jnp.einsum("bqd,bkd->bqk", q_n, k_nope)
                      + jnp.einsum("bqd,bkd->bqk", q_p, k_r)) * scale
            scores = jnp.where(rows[:, None] >= position[None, :], scores,
                               -jnp.inf)
            return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1),
                              v)

        out = jax.lax.map(queries, jnp.arange(0, t, block))  # [blocks, b, block, dv]
        return out.transpose(1, 0, 2, 3).reshape(b, t, dv)

    if checkpoint:
        one_head = jax.checkpoint(one_head)
    o = jax.lax.map(one_head, (q, up)).transpose(1, 2, 0, 3)  # [b, t, h, dv]
    return o.reshape(b, t, heads * dv) @ w["wo"]


def _experts(w, u, cfg, routing, checkpoint):
    """(the held experts' part of the result plus the shared expert's, the
    free selection, the margin, every expert's excess load under the
    selection used). ``routing`` [B, T, k] replaces the selection where
    given."""
    k = cfg["num_experts_per_tok"]
    total = w["router"].shape[1]
    scores = jax.nn.sigmoid(u @ w["router"])
    top, free = jax.lax.top_k(scores + w["expert_bias"].astype(u.dtype), k + 1)
    margin = (top[..., k - 1] - top[..., k]).astype(jnp.float32)
    free = free[..., :k]
    sel = free if routing is None else routing
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    weight = picked / (jnp.sum(picked, axis=-1, keepdims=True)
                       + jnp.asarray(WEIGHT_EPS, u.dtype))
    weight = weight * jnp.asarray(cfg["routed_scaling_factor"], u.dtype)

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
    out = out + _swiglu(u, w["shared_in"], w["shared_out"])
    chosen = jnp.sum(sel[..., None] == jnp.arange(total), axis=(0, 1, 2))
    excess = chosen.astype(jnp.float32) / (sel.size / total) - 1.0
    return out, free, margin, excess


def _block(ffn, w, h, cfg, routing, checkpoint):
    eps = cfg["rms_norm_eps"]
    h = h + _mla(w, _rms(h, w["norm1"], eps), cfg, checkpoint)
    y = _rms(h, w["norm2"], eps)
    if ffn == "dense":
        return h + _swiglu(y, w["w_in"], w["w_out"]), None
    out, *said = _experts(w, y, cfg, routing, checkpoint)
    return h + out, tuple(said)


def _mtp_input(p, h_last, ahead, cfg):
    """``u``: the next token's embedding and the main model's last stream,
    each normed, side by side through ``eh_proj``."""
    w, eps = p["mtp_0"], cfg["rms_norm_eps"]
    stream = h_last
    return jnp.concatenate([_rms(p["embed"][ahead], w["enorm"], eps),
                            _rms(stream, w["hnorm"], eps)], axis=-1) @ w["eh_proj"]


def hidden(params, tokens, cfg, checkpoint=False, compute_dtype=jnp.float32,
           routing=None, ahead=None):
    """(the final norm's output [B, T, D], the free selection [expert layers,
    B, T, k], the margins [expert layers, B, T], the excess loads [expert
    layers, E], the state the module's head reads or None). ``ahead`` [B, T]:
    the tokens one place on, which the module reads; None: no module."""
    p = _cast(params["params"], compute_dtype)
    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        told = []

        def run(ffn, w, h):
            forced = None
            if ffn == "experts" and routing is not None:
                forced = routing[len(told)]

            def block(w, h, forced):
                return _block(ffn, w, h, cfg, forced, checkpoint)

            if checkpoint:
                block = jax.checkpoint(block)
            h, said = block(w, h, forced)
            if said is not None:
                told.append(said)
            return h

        for i, ffn in enumerate(cfg["ffn_types"]):
            h = run(ffn, p[f"layer_{i}"], h)
        g = None
        if ahead is not None and cfg["mtp_weight"]:
            g = run("experts", p["mtp_0"], _mtp_input(p, h, ahead, cfg))
            g = _rms(g, p["mtp_0"]["final_norm"], cfg["rms_norm_eps"])
        selection, margin, excess = (jnp.stack(x) for x in zip(*told))
        return (_rms(h, p["final_norm"], cfg["rms_norm_eps"]), selection,
                margin, excess, g)


def forward(params, tokens, cfg, routing=None):
    """Logits [B, T, V], whole (small sizes)."""
    return logits_of(params, hidden(params, tokens, cfg, routing=routing)[0],
                     cfg)


def loss(params, x, cfg, token_block=0, checkpoint=False,
         compute_dtype=jnp.float32, with_states=False, routing=None):
    """(the loss, aux) on x int32 [B, T+1]: inputs x[:, :-1], the main head's
    targets x[:, 1:], the module's x[:, 2:] from its rows 0 .. T-2. ``aux``:
    ``selection``, ``margin``, ``excess``, ``main_loss``, ``mtp_loss`` and,
    ``with_states``, the final norm's output (``hidden``) and the module's
    (``mtp_hidden``)."""
    h, selection, margin, excess, g = hidden(
        params, x[:, :-1], cfg, checkpoint, compute_dtype, routing, x[:, 1:])
    main = jnp.mean(_cross_entropy(params, h, x[:, 1:], cfg, token_block,
                                   compute_dtype)).astype(jnp.float32)
    aux = {"selection": selection, "margin": margin, "excess": excess,
           "main_loss": main, "mtp_loss": jnp.zeros((), jnp.float32)}
    if g is not None:
        # row T - 1 predicts a token the row does not hold: left out. (The
        # rows are padded back to T so that the blocks divide them.)
        targets = jnp.pad(x[:, 2:], ((0, 0), (0, 1)))
        ce = _cross_entropy(params, g, targets, cfg, token_block,
                            compute_dtype)
        aux["mtp_loss"] = jnp.mean(ce[:, :-1]).astype(jnp.float32)
    if with_states:
        aux["hidden"] = h
        if g is not None:
            aux["mtp_hidden"] = g
    return main + cfg["mtp_weight"] * aux["mtp_loss"], aux


def loss_and_grads(params, x, cfg, token_block=0, checkpoint=False,
                   compute_dtype=jnp.float32, with_states=False, routing=None):
    (value, aux), grads = jax.value_and_grad(
        lambda p: loss(p, x, cfg, token_block, checkpoint, compute_dtype,
                       with_states, routing), has_aux=True)(params)
    # the bias has no gradient of the loss; what the balancing rule reads
    # stands in its place
    layers = [f"layer_{i}" for i, ffn in enumerate(cfg["ffn_types"])
              if ffn == "experts"] + (["mtp_0"] if cfg["mtp_weight"] else [])
    for name, excess in zip(layers, aux.pop("excess")):
        grads["params"][name]["expert_bias"] = excess
    return value, aux, grads
