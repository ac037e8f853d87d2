"""Olmo-Hybrid (``olmo_hybrid``) forward, loss and gradients for ONE CHIP'S
SHARE of every mixer's heads, written from the published ``config.json``'s
keys (the catalog's row) and what is known of the source's modeling file and
of Gated DeltaNet (arXiv:2412.06464), from memory: no network here. Against
the parameter tree ``raydp_tpu.models.HybridLM`` creates for this family:
``embed`` [V, D], ``head`` [D, V] (untied), ``final_norm``, and ``layer_<i>``
with ``norm1``, ``norm2``, ``w_in`` [D, gate | up], ``w_out`` [F, D] and, by
``layer_types``, a delta-rule mixer (``wq``, ``wk`` [D, H Dk], ``wv``, ``wg``
[D, H Dv], ``wa``, ``wb`` [D, H], ``conv_w`` [K, q | k | v channels],
``A_log``, ``dt_bias`` [H], ``gate_norm`` [Dv], ``wo`` [H Dv, D]) or an
attention mixer (``wq``, ``wk``, ``wv`` [D, heads x Dh], ``q_norm``,
``k_norm`` [heads x Dh], ``wo``); matrices are [in, out]; H and ``heads`` are
the heads HELD here. Imports nothing from ``raydp_tpu``.

A ``linear_attention`` layer on the stream ``a`` [T, D]::

    q^, k^, v^ = silu(conv(W_q a)), silu(conv(W_k a)), silu(conv(W_v a))
    q_t = l2norm_head(q^_t) / sqrt(Dk);  k_t = l2norm_head(k^_t);  v_t = v^_t
    beta_t  = 2 sigmoid(W_b a)_t            # linear_allow_neg_eigval
    alpha_t = exp(-exp(A_log) softplus((W_a a)_t + dt_bias))
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T;  S_0 = 0
    o_t = S_t q_t
    y_t = W_o [ RMSNorm_head(o_t; gate_norm) * silu((W_g a)_t) ]

the depthwise convolution causal, ``K`` taps, no bias; the recurrence PER
TOKEN exactly as written (decay, erase, write, read: a ``lax.scan`` over
tokens; no chunks, no triangular solve: it shares no algebra with
``ops/delta_rule.py``). A ``full_attention`` layer: ``q = RMSNorm(W_q a;
q_norm)``, ``k = RMSNorm(W_k a; k_norm)``, each norm's statistic over ALL the
heads held here, no positions, causal softmax of ``q.k / sqrt(Dh)`` a head,
``W_o``. The block, the Olmo family's: a sub-layer's OUTPUT is normed::

    h = h + RMSNorm(Mixer(h); norm1);  h = h + RMSNorm(W_out(silu(g) u); norm2)
    logits = RMSNorm(h; final_norm) W_head

Plain ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernels. Departures, none of which changes the arithmetic: the recurrence is
a two-level ``lax.scan`` (``SCAN_BLOCK`` tokens inside, recomputed in the
backward pass: a plain scan's gradient stores a [H, Dv, Dk] state a token,
1.1 MB x 8192 a layer at the published widths); ``checkpoint=True`` wraps a
block and a head in ``jax.checkpoint``; ``token_block`` computes the
cross-entropy over blocks of tokens. ``compute_dtype`` (default float32)
exists only to produce the benchmark's second reading: the same reference
with every matmul, activation, decay, state, logit and the loss in a lower
precision.

``adamw_step`` is ``reference/granite_hybrid.py``'s AdamW (in place on the
host, the leaves side by side on a few threads; the arithmetic and its order
are its ``_adamw_leaf``'s, bit for bit) with each leaf worked in BLOCKS of
rows, ``ADAMW_BLOCK`` elements each: a thread's two temporaries are 32 MB and not twice
a leaf (the SwiGLU's ``w_in`` is 338 MB here, and eight threads' temporaries
were 5 GB of the host's memory beside three copies of the parameters, two
moments and a gradient: PERF.md, PR 45).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.granite_hybrid import (  # noqa: F401 - the driver's
    _adamw_leaf, _cast, _mlp, _rms, adamw_init)

SCAN_BLOCK = 64
L2_EPS = 1e-6
ADAMW_BLOCK = 1 << 22  # elements a temporary of the host's AdamW holds


def config_of(config: dict) -> dict:
    """What the equations read of a published configuration: the layers
    built are ``layer_types[first_layer : first_layer + num_hidden_layers]``;
    ``head_dim`` is a key of its own where a share of the heads is held."""
    keys = ("num_hidden_layers", "num_attention_heads", "num_key_value_heads",
            "linear_num_key_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "linear_allow_neg_eigval", "rms_norm_eps")
    cfg = {k: config[k] for k in keys}
    first = config.get("share", {}).get("first_layer", 0)
    cfg["layer_types"] = tuple(
        config["layer_types"][first:first + cfg["num_hidden_layers"]])
    cfg["head_dim"] = config.get("head_dim") or (
        config["hidden_size"] // config["num_attention_heads"])
    return cfg


def _attention(w, x, cfg, checkpoint):
    b, t, _ = x.shape
    heads, kv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    group, eps = heads // kv, cfg["rms_norm_eps"]
    # the norms' statistics over the whole projection held here
    q = _rms(x @ w["wq"], w["q_norm"], eps)
    k = _rms(x @ w["wk"], w["k_norm"], eps)
    q = q.reshape(b, t, kv, group, dh).transpose(2, 0, 3, 1, 4)
    k = k.reshape(b, t, kv, dh).transpose(2, 0, 1, 3)
    v = (x @ w["wv"]).reshape(b, t, kv, dh).transpose(2, 0, 1, 3)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_group(qkv):
        q_g, k_g, v_g = qkv  # [b, group, t, dh], [b, t, dh], [b, t, dh]
        scores = jnp.einsum("bgqd,bkd->bgqk", q_g, k_g) * jnp.asarray(
            dh ** -0.5, x.dtype)
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bgqd", jax.nn.softmax(scores, axis=-1),
                          v_g)

    if checkpoint:
        one_group = jax.checkpoint(one_group)
    out = jax.lax.map(one_group, (q, k, v))  # [kv, b, group, t, dh]
    return out.transpose(1, 3, 0, 2, 4).reshape(b, t, heads * dh) @ w["wo"]


def _recurrence(q, k, v, alpha, beta, scan_block):
    """The per-token delta rule. ``q``, ``k`` [b, t, h, dk]; ``v``
    [b, t, h, dv]; ``alpha``, ``beta`` [b, t, h]. Returns o [b, t, h, dv]."""
    b, t, h, dk = q.shape
    dv = v.shape[-1]

    def token(state, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        state = a_t[..., None, None] * state  # decay
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


def _delta(w, a, cfg):
    b, t, _ = a.shape
    heads, dk, dv = (cfg["linear_num_key_heads"], cfg["linear_key_head_dim"],
                     cfg["linear_value_head_dim"])
    taps = cfg["linear_conv_kernel_dim"]

    def conv_silu(x, conv_w):
        # depthwise, causal: tap K - 1 is the current token's
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(conv_w[i] * padded[:, i:i + t]
                               for i in range(taps)))

    def l2(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                            + jnp.asarray(L2_EPS, x.dtype))

    keys = heads * dk
    q = conv_silu(a @ w["wq"], w["conv_w"][:, :keys])
    k = conv_silu(a @ w["wk"], w["conv_w"][:, keys:2 * keys])
    v = conv_silu(a @ w["wv"], w["conv_w"][:, 2 * keys:])
    q = l2(q.reshape(b, t, heads, dk)) * jnp.asarray(dk ** -0.5, a.dtype)
    k = l2(k.reshape(b, t, heads, dk))
    scale = 2.0 if cfg["linear_allow_neg_eigval"] else 1.0
    beta = jnp.asarray(scale, a.dtype) * jax.nn.sigmoid(a @ w["wb"])
    alpha = jnp.exp(-jnp.exp(w["A_log"])
                    * jax.nn.softplus(a @ w["wa"] + w["dt_bias"]))
    o = _recurrence(q, k, v.reshape(b, t, heads, dv), alpha, beta, SCAN_BLOCK)
    o = _rms(o, w["gate_norm"], cfg["rms_norm_eps"]).reshape(b, t, heads * dv)
    return (o * jax.nn.silu(a @ w["wg"])) @ w["wo"]


def _block(kind, w, h, cfg, checkpoint):
    eps = cfg["rms_norm_eps"]
    mixed = (_delta(w, h, cfg) if kind == "linear_attention"
             else _attention(w, h, cfg, checkpoint))
    h = h + _rms(mixed, w["norm1"], eps)
    return h + _rms(_mlp(w, h), w["norm2"], eps)


def hidden(params, tokens, cfg, checkpoint=False, compute_dtype=jnp.float32):
    """The final norm's output [B, T, D]."""
    p = _cast(params["params"], compute_dtype)
    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        for i, kind in enumerate(cfg["layer_types"]):
            def block(w, h, kind=kind):
                return _block(kind, w, h, cfg, checkpoint)

            if checkpoint:
                block = jax.checkpoint(block)
            h = block(p[f"layer_{i}"], h)
        return _rms(h, p["final_norm"], cfg["rms_norm_eps"])


def logits_of(params, h, cfg, compute_dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        return h @ params["params"]["head"].astype(compute_dtype)


def forward(params, tokens, cfg):
    """Logits [B, T, V], whole (small sizes)."""
    return logits_of(params, hidden(params, tokens, cfg), cfg)


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
         compute_dtype=jnp.float32, with_states=False):
    """(mean next-token cross-entropy, aux) on x int32 [B, T+1]: inputs
    x[:, :-1], targets x[:, 1:]. ``with_states`` puts the final norm's
    output (``hidden``) into ``aux``, for a comparison of the logits."""
    h = hidden(params, x[:, :-1], cfg, checkpoint, compute_dtype)
    ce = _cross_entropy(params, h, x[:, 1:], cfg, token_block, compute_dtype)
    return jnp.mean(ce).astype(jnp.float32), (
        {"hidden": h} if with_states else {})


def loss_and_grads(params, x, cfg, token_block=0, checkpoint=False,
                   compute_dtype=jnp.float32, with_states=False):
    (value, aux), grads = jax.value_and_grad(
        lambda p: loss(p, x, cfg, token_block, checkpoint, compute_dtype,
                       with_states), has_aux=True)(params)
    return value, aux, grads


def _adamw_leaf_in_blocks(p, g, m, v, t, learning_rate, b1, b2, weight_decay,
                          eps):
    """``granite_hybrid._adamw_leaf`` on blocks of a leaf's ROWS, about
    ``ADAMW_BLOCK`` elements each: the same elementwise arithmetic in the
    same order, into ``p``, ``m`` and ``v`` themselves (a block is a view,
    whatever order the leaf lies in: an array fetched from the chip may come
    back column-major, and its copies follow it). A block keeps the leaf's
    rank, so the decay still goes by it."""
    if p.ndim == 0 or p.size <= ADAMW_BLOCK:
        return _adamw_leaf(p, g, m, v, t, learning_rate, b1, b2, weight_decay,
                           eps)
    rows = max(1, ADAMW_BLOCK // (p.size // p.shape[0]))
    for start in range(0, p.shape[0], rows):
        _adamw_leaf(*(a[start:start + rows] for a in (p, g, m, v)),
                    t, learning_rate, b1, b2, weight_decay, eps)


def adamw_step(leaves, grads, state, learning_rate, b1, b2, weight_decay,
               eps=1e-8):
    """One AdamW step IN PLACE, as ``granite_hybrid.adamw_step`` (decay on
    every parameter with two or more axes, added to the Adam direction),
    block by block. Returns (leaves, state)."""
    from concurrent.futures import ThreadPoolExecutor

    t = state["count"] = state["count"] + 1
    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(
            lambda args: _adamw_leaf_in_blocks(
                *args, t, learning_rate, b1, b2, weight_decay, eps),
            zip(leaves, grads, state["m"], state["v"])))
    return leaves, state
