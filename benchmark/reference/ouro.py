"""Ouro / LoopLM forward, loss and gradients, written from the description
(arXiv:2510.25741 and the source's ``modeling_ouro.py``, from memory: no
network here), against the parameter tree ``raydp_tpu.models.LoopLM``
creates (``embed`` [V, D], ``layer_<i>`` {wq wk wv wo w_gate w_up w_down
norm1..4}, ``final_norm``, ``head`` [D, V], ``gate`` {w, b}; matrices are
[in, out]). Imports nothing from ``raydp_tpu``.

Plain ``jax.numpy``, float32, ``jax.default_matmul_precision("highest")``,
no kernels; the loop over the R steps is a Python ``for`` over the SAME L
layers. ``cfg`` holds the published keys: ``num_attention_heads``,
``rope_theta``, ``rms_norm_eps``, ``total_ut_steps``, and ``entropy_beta``.

Departures, none of which changes the arithmetic: ``checkpoint=True`` wraps
a block in ``jax.checkpoint`` and ``token_block`` computes an exit's
cross-entropy over blocks of tokens, so that loss and gradients fit at the
published widths. ``compute_dtype`` (default float32) exists only to produce
the benchmark's second reading: the same reference with every matmul,
activation, logit and the loss in a lower precision.

``adamw_step`` is the optimizer the configuration assumes, written out on
lists of numpy float32 arrays (the host's memory: parameters, two moments
and a gradient of the real size are 8 GB): decay on every parameter with two
or more axes, none on norm gains or the gate.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x [B, H, T, D]; rotate-half over the whole head."""
    t, d = x.shape[2], x.shape[3]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1).astype(x.dtype)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1).astype(x.dtype)
    half = d // 2
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rotated * sin


def _attention(w, x, cfg):
    b, t, d = x.shape
    heads = cfg["num_attention_heads"]

    def split(z):
        return z.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)

    q = _rope(split(x @ w["wq"]), cfg["rope_theta"])
    k = _rope(split(x @ w["wk"]), cfg["rope_theta"])
    v = split(x @ w["wv"])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) / jnp.sqrt(
        jnp.asarray(d // heads, x.dtype))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v)
    return out.transpose(0, 2, 1, 3).reshape(b, t, d) @ w["wo"]


def _mlp(w, x):
    return (jax.nn.silu(x @ w["w_gate"]) * (x @ w["w_up"])) @ w["w_down"]


def _block(w, h, cfg):
    eps = cfg["rms_norm_eps"]
    a = h + _rms(_attention(w, _rms(h, w["norm1"], eps), cfg), w["norm2"], eps)
    return a + _rms(_mlp(w, _rms(a, w["norm3"], eps)), w["norm4"], eps)


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def hidden_and_gates(params, tokens, cfg, checkpoint=False,
                     compute_dtype=jnp.float32):
    """([h_1..h_R] each [B, T, D], [lam_1..lam_R] each [B, T])."""
    p = _cast(params["params"], compute_dtype)
    layers = [p[f"layer_{i}"] for i in range(
        sum(1 for k in p if k.startswith("layer_")))]
    def block(w, h):
        return _block(w, h, cfg)

    if checkpoint:
        block = jax.checkpoint(block)
    with jax.default_matmul_precision("highest"):
        h = p["embed"][tokens]
        hs, lams = [], []
        for _ in range(int(cfg["total_ut_steps"])):
            for w in layers:
                h = block(w, h)
            h = _rms(h, p["final_norm"], cfg["rms_norm_eps"])
            hs.append(h)
            lams.append(jax.nn.sigmoid(h @ p["gate"]["w"] + p["gate"]["b"]))
    return hs, lams


def logits_of(params, h, compute_dtype=jnp.float32):
    with jax.default_matmul_precision("highest"):
        return h @ params["params"]["head"].astype(compute_dtype)


def forward(params, tokens, cfg):
    """(logits [R, B, T, V], lam [R, B, T]): every exit whole (small sizes)."""
    hs, lams = hidden_and_gates(params, tokens, cfg)
    return jnp.stack([logits_of(params, h) for h in hs]), jnp.stack(lams)


def exit_distribution(lams):
    """p_t = lam_t prod_{j<t}(1 - lam_j) for t < R; p_R takes the rest."""
    ps, survive = [], jnp.ones_like(lams[0])
    for lam in lams[:-1]:
        ps.append(lam * survive)
        survive = survive * (1.0 - lam)
    ps.append(survive)
    return ps


def _cross_entropy(params, h, targets, token_block, compute_dtype):
    """Per-token CE [B, T] of one exit; ``token_block`` tokens at a time."""
    b, t, d = h.shape

    def ce(h_blk, y_blk):
        z = logits_of(params, h_blk, compute_dtype)
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
    """(loss, {"exit_loss": [R], "exit_mass": [R]}) on x int32 [B, T+1]:
    inputs x[:, :-1], next tokens x[:, 1:]. ``with_states`` adds every loop
    step's closing state (``hidden``) and exit distribution (``mass``) to
    the second value, for a comparison exit by exit."""
    tokens, targets = x[:, :-1], x[:, 1:]
    hs, lams = hidden_and_gates(params, tokens, cfg, checkpoint, compute_dtype)
    ps = exit_distribution(lams)
    ces = [_cross_entropy(params, h, targets, token_block, compute_dtype)
           for h in hs]
    expected = sum(p * ce for p, ce in zip(ps, ces))
    entropy = -sum(p * jnp.log(jnp.maximum(p, 1e-30)) for p in ps)
    total = jnp.mean(expected) - cfg["entropy_beta"] * jnp.mean(entropy)
    aux = {"exit_loss": jnp.stack([jnp.mean(ce) for ce in ces]),
           "exit_mass": jnp.stack([jnp.mean(p) for p in ps])}
    if with_states:
        aux.update(hidden=jnp.stack(hs), mass=jnp.stack(ps))
    return total.astype(jnp.float32), aux


def loss_and_grads(params, x, cfg, token_block=0, checkpoint=False,
                   compute_dtype=jnp.float32, with_states=False):
    (value, aux), grads = jax.value_and_grad(
        lambda p: loss(p, x, cfg, token_block, checkpoint, compute_dtype,
                       with_states), has_aux=True)(params)
    return value, aux, grads


def adamw_init(leaves):
    return {"count": 0, "m": [np.zeros_like(a) for a in leaves],
            "v": [np.zeros_like(a) for a in leaves]}


def adamw_step(leaves, grads, state, learning_rate, b1, b2, weight_decay,
               eps=1e-8):
    """One AdamW step (Loshchilov & Hutter: the decay is added to the Adam
    direction, not to the gradient). Returns (new leaves, new state)."""
    t = state["count"] + 1
    out, ms, vs = [], [], []
    for p, g, m, v in zip(leaves, grads, state["m"], state["v"]):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * g * g
        direction = (m / (1.0 - b1 ** t)) / (np.sqrt(v / (1.0 - b2 ** t)) + eps)
        if p.ndim >= 2:
            direction = direction + weight_decay * p
        out.append((p - learning_rate * direction).astype(np.float32))
        ms.append(m.astype(np.float32))
        vs.append(v.astype(np.float32))
    return out, {"count": t, "m": ms, "v": vs}
