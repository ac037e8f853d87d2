"""Granite-4.0-H (``granitemoehybrid`` with no experts) forward, loss and
gradients, written from the published ``config.json``'s keys and the source's
``modeling_granitemoehybrid.py`` (from memory: no network here), against the
parameter tree ``raydp_tpu.models.HybridLM`` creates: ``embed`` [V, D] (the
head is its transpose), ``final_norm``, and ``layer_<i>`` with ``norm1``,
``norm2``, ``w_in`` [D, 2F] (gate | up), ``w_out`` [F, D] and, by
``layer_types[i]``, an attention mixer (``wq`` [D, D], ``wk``, ``wv``
[D, KV x Dh], ``wo``) or a Mamba-2 mixer (``in_proj`` [D, z | xBC | dt],
``conv_w`` [K, C], ``conv_b``, ``dt_bias``, ``A_log``, ``D``, ``gate_norm``,
``out_proj``); matrices are [in, out]. Imports nothing from ``raydp_tpu``.

Plain ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``, no
kernels. The Mamba-2 recurrence is the PER-TOKEN one, as the equations have
it (``S_t = a_t S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t``), not the
chunked dual form the program runs. Attention is a full softmax over all
keys, one K/V head's group of query heads at a time. ``cfg`` is
``config_of(configuration)``: the published keys the equations use.

Departures, none of which changes the arithmetic: the recurrence is a
two-level ``lax.scan`` (``scan_block`` tokens inside, recomputed in the
backward pass: a plain scan's gradient stores a [H, P, N] state a token, 2 MB
x 8192 a layer at the published widths); ``checkpoint=True`` wraps a block
and a head group in ``jax.checkpoint``; ``token_block`` computes the
cross-entropy over blocks of tokens. ``compute_dtype`` (default float32)
exists only to produce the benchmark's second reading: the same reference
with every matmul, activation, decay, state, logit and the loss in a lower
precision.

``adamw_step`` is the optimizer the configuration assumes, written out on
lists of numpy float32 arrays (the host's memory): decay on every parameter
with two or more axes; none on a vector (norm gains, ``A_log``, ``D``,
``dt_bias``, ``conv_b``). It works IN PLACE, the leaves side by side on a few
threads: parameters, two moments and a gradient of the real size are 13.6
GB, a second copy of the first three beside them was more than the chip's
host had left, and one thread's fresh temporaries took most of part (a)'s
time (PR 31). The arithmetic and its order are ``reference/ouro.py``'s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

SCAN_BLOCK = 64


def config_of(config: dict) -> dict:
    """What the equations read of a published configuration."""
    keys = ("layer_types", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "mamba_n_heads", "mamba_d_head",
            "mamba_d_state", "mamba_d_conv", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier", "logits_scaling",
            "rms_norm_eps")
    cfg = {k: config[k] for k in keys}
    cfg["layer_types"] = tuple(cfg["layer_types"][:cfg["num_hidden_layers"]])
    return cfg


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _attention(w, x, cfg, checkpoint):
    b, t, d = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh, group = d // heads, heads // kv
    # K/V head g serves query heads g x group .. (g + 1) x group - 1
    q = (x @ w["wq"]).reshape(b, t, kv, group, dh).transpose(2, 0, 3, 1, 4)
    k = (x @ w["wk"]).reshape(b, t, kv, dh).transpose(2, 0, 1, 3)
    v = (x @ w["wv"]).reshape(b, t, kv, dh).transpose(2, 0, 1, 3)
    causal = jnp.tril(jnp.ones((t, t), bool))

    def one_group(qkv):
        q_g, k_g, v_g = qkv  # [b, group, t, dh], [b, t, dh], [b, t, dh]
        scores = jnp.einsum("bgqd,bkd->bgqk", q_g, k_g) * jnp.asarray(
            cfg["attention_multiplier"], x.dtype)
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bgqk,bkd->bgqd", jax.nn.softmax(scores, axis=-1),
                          v_g)

    if checkpoint:
        one_group = jax.checkpoint(one_group)
    out = jax.lax.map(one_group, (q, k, v))  # [kv, b, group, t, dh]
    return out.transpose(1, 3, 0, 2, 4).reshape(b, t, d) @ w["wo"]


def _recurrence(x, dt, a, bm, cm, d_skip, scan_block):
    """The per-token recurrence. ``x`` [b, t, h, p]; ``dt``, ``a`` [b, t, h];
    ``bm``, ``cm`` [b, t, n]; ``d_skip`` [h]. Returns y [b, t, h, p]."""
    b, t, h, p = x.shape
    n = bm.shape[-1]

    def token(state, inputs):
        x_t, dt_t, a_t, b_t, c_t = inputs
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        y_t = jnp.einsum("bhpn,bn->bhp", state, c_t) + d_skip[:, None] * x_t
        return state, y_t

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    inner = scan_block if t % scan_block == 0 else t
    time_major = [z.swapaxes(0, 1).reshape((t // inner, inner) + z.shape[:1]
                                           + z.shape[2:])
                  for z in (x, dt, a, bm, cm)]
    _, y = jax.lax.scan(block, jnp.zeros((b, h, p, n), x.dtype), time_major)
    return y.reshape((t, b, h, p)).swapaxes(0, 1)


def _mamba(w, u, cfg):
    b, t, _ = u.shape
    heads, p, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    k, inner = cfg["mamba_d_conv"], heads * p
    proj = u @ w["in_proj"]
    z, xbc, dt = (proj[..., :inner], proj[..., inner:2 * inner + 2 * n],
                  proj[..., 2 * inner + 2 * n:])
    # depthwise causal convolution: tap K - 1 is the current token's
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = w["conv_b"] + sum(w["conv_w"][i] * padded[:, i:i + t]
                             for i in range(k))
    xbc = jax.nn.silu(conv)
    x, bm, cm = (xbc[..., :inner].reshape(b, t, heads, p),
                 xbc[..., inner:inner + n], xbc[..., inner + n:])
    dt = jax.nn.softplus(dt + w["dt_bias"])
    a = jnp.exp(-jnp.exp(w["A_log"]) * dt)
    y = _recurrence(x, dt, a, bm, cm, w["D"], SCAN_BLOCK).reshape(b, t, inner)
    # the gate BEFORE the norm, the norm over all of the inner width
    return _rms(y * jax.nn.silu(z), w["gate_norm"], cfg["rms_norm_eps"]
                ) @ w["out_proj"]


def _mlp(w, x):
    gu = x @ w["w_in"]
    half = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :half]) * gu[..., half:]) @ w["w_out"]


def _block(kind, w, h, cfg, checkpoint):
    eps, res = cfg["rms_norm_eps"], jnp.asarray(cfg["residual_multiplier"],
                                                h.dtype)
    y = _rms(h, w["norm1"], eps)
    mixed = (_mamba(w, y, cfg) if kind == "mamba"
             else _attention(w, y, cfg, checkpoint))
    h = h + res * mixed
    return h + res * _mlp(w, _rms(h, w["norm2"], eps))


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def hidden(params, tokens, cfg, checkpoint=False, compute_dtype=jnp.float32):
    """The final norm's output [B, T, D]."""
    p = _cast(params["params"], compute_dtype)
    with jax.default_matmul_precision("highest"):
        h = jnp.asarray(cfg["embedding_multiplier"], compute_dtype
                        ) * p["embed"][tokens]
        for i, kind in enumerate(cfg["layer_types"]):
            def block(w, h, kind=kind):
                return _block(kind, w, h, cfg, checkpoint)

            if checkpoint:
                block = jax.checkpoint(block)
            h = block(p[f"layer_{i}"], h)
        return _rms(h, p["final_norm"], cfg["rms_norm_eps"])


def logits_of(params, h, cfg, compute_dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        embed = params["params"]["embed"].astype(compute_dtype)
        return (h @ embed.T) / jnp.asarray(cfg["logits_scaling"], compute_dtype)


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


def adamw_init(leaves):
    return {"count": 0, "m": [np.zeros_like(a) for a in leaves],
            "v": [np.zeros_like(a) for a in leaves]}


def _adamw_leaf(p, g, m, v, t, learning_rate, b1, b2, weight_decay, eps):
    """One leaf's step, written into ``p``, ``m`` and ``v`` themselves; the
    arithmetic and its order are ``reference/ouro.py:adamw_step``'s."""
    one, two = np.empty_like(p), np.empty_like(p)
    np.multiply(g, 1.0 - b1, out=one)
    m *= b1
    m += one
    np.multiply(g, 1.0 - b2, out=one)
    one *= g
    v *= b2
    v += one
    np.divide(v, 1.0 - b2 ** t, out=one)
    np.sqrt(one, out=one)
    one += eps
    np.divide(m, 1.0 - b1 ** t, out=two)
    two /= one  # the Adam direction
    if p.ndim >= 2:
        np.multiply(p, weight_decay, out=one)
        two += one
    two *= learning_rate
    p -= two


def adamw_step(leaves, grads, state, learning_rate, b1, b2, weight_decay,
               eps=1e-8):
    """One AdamW step (Loshchilov & Hutter: the decay is added to the Adam
    direction, not to the gradient) IN PLACE: every array of ``leaves``,
    ``state["m"]`` and ``state["v"]`` is overwritten with its new value (a
    caller that needs the old parameters copies them first), the leaves
    side by side on a few threads (numpy releases the lock inside its
    loops). Returns (leaves, state)."""
    from concurrent.futures import ThreadPoolExecutor

    t = state["count"] = state["count"] + 1
    with ThreadPoolExecutor(max_workers=8) as pool:
        list(pool.map(
            lambda args: _adamw_leaf(*args, t, learning_rate, b1, b2,
                                     weight_decay, eps),
            zip(leaves, grads, state["m"], state["v"])))
    return leaves, state
