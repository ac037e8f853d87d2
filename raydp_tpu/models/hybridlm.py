"""Hybrid state-space / attention decoder LM (the ``granitemoehybrid`` family
with no experts: Mamba-2 layers and grouped-query attention layers in the
order ``layer_types`` gives, each followed by one dense SwiGLU).

::

    h = embedding_multiplier E[x]
    for kind in layer_types:
      h = h + residual_multiplier Mixer_kind(RMSNorm(h))     # pre-norm only
      [g, u] = W_in RMSNorm(h);  h = h + residual_multiplier W_out(silu(g) u)
    logits = RMSNorm_f(h) E^T / logits_scaling               # tied head
    loss = mean next-token cross-entropy

``attention``: q of ``num_heads`` heads, k and v of ``num_kv_heads`` (each
serves ``num_heads / num_kv_heads`` consecutive query heads), no bias, no
positions, causal softmax of ``attention_multiplier q.k``. It runs through
the repo's ``_attend`` (``attn_impl="flash"`` on the chip): q is multiplied
by ``attention_multiplier sqrt(head_dim)`` beforehand, so that the kernels'
own ``head_dim ** -0.5`` gives the multiplier, and K and V are repeated to
the query heads in HBM.

``mamba`` (Mamba-2; H heads of P, one group, state N)::

    [z | xBC | dt] = W_in u
    xBC = silu(conv1d_causal(xBC, k, depthwise, bias));  [x | B | C] = xBC
    dt = softplus(dt + dt_bias);  a_t = exp(-exp(A_log) dt_t)
    S_t = a_t S_{t-1} + dt_t x_t (x) B_t;  y_t = S_t C_t + D x_t
    out = W_out RMSNorm(y silu(z))           # the gate BEFORE the norm

The recurrence is ``ops.ssd.ssd_chunk_scan`` (chunked; matmuls inside a
chunk). Parameters are float32; ``dtype`` is the compute dtype (bf16 on the
chip): matmul operands and the residual stream; norm statistics, the
convolution, dt, the decays and the state, the residual additions, the
logits and the loss are float32.

The estimator trains it with ``loss="model"`` exactly as it trains
``LoopLM``: ``loss(x)`` takes the int32 ``[B, T+1]`` sequence column whole.
Blocks are recomputed from their inputs (``remat``) but for
``REMAT_KEEPS``. The loss is ``looplm.chunked_cross_entropy`` on the tied
embedding: each chunk of ``loss_chunk`` tokens computes its logits once and
takes their gradient in the forward sweep; it holds one chunk of float32
logits, the gradient back to the final norm's output and one float32
accumulator of the embedding's own [V, D] shape, and recomputes nothing.
"""

from __future__ import annotations

import math
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from raydp_tpu.models.looplm import (
    LOSS_FACTS, chunked_cross_entropy, looplm_optimizer, rms_norm)
from raydp_tpu.models.transformer import _attend
from raydp_tpu.ops.flash_attention import SAVED_RESIDUALS
from raydp_tpu.ops.ssd import ssd_chunk_scan

MAMBA, ATTENTION = "mamba", "attention"
# what a recomputed block keeps from its forward pass: the flash kernel's
# output and log-sum-exp (an attention layer; with both kept the recomputed
# kernel call is dead code) and ``w_out``'s output (every layer). A Mamba
# mixer keeps nothing: the scan's backward pass needs the decays, the
# scores and the chunk states, not ``y``, so a kept ``ssd_out`` would spare
# two of its five products for 67 MB a layer and row
REMAT_KEEPS = SAVED_RESIDUALS + ("mlp_out",)


def _inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


class HybridLM(nn.Module):
    vocab_size: int
    layer_types: Sequence[str] = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    hidden_size: int = 2048
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 8192
    mamba_heads: int = 64
    mamba_head_dim: int = 64
    mamba_state: int = 128
    mamba_conv: int = 4
    mamba_chunk: int = 256
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_eps: float = 1e-5
    attn_impl: str = "full"  # "flash" on the chip; as LoopLM's
    dtype: Any = jnp.bfloat16  # compute dtype; parameters are float32
    remat: bool = True  # recompute each block in the backward pass
    loss_chunk: int = 2048  # tokens of logits held at a time; 0: all

    @classmethod
    def from_config(cls, config: dict, **kw):
        """From the published ``config.json``'s keys (``granitemoehybrid``):
        the first ``num_hidden_layers`` entries of ``layer_types``. What the
        model does not build is refused, not ignored."""
        refused = {
            "num_local_experts": 0, "mamba_n_groups": 1,
            "mamba_proj_bias": False, "attention_bias": False,
            "mamba_conv_bias": True, "tie_word_embeddings": True,
            "position_embedding_type": "nope", "hidden_act": "silu"}
        for key, only in refused.items():
            if config.get(key, only) != only:
                raise ValueError(f"HybridLM builds {key}={only!r} only, "
                                 f"not {config[key]!r}")
        if config["mamba_expand"] * config["hidden_size"] != (
                config["mamba_n_heads"] * config["mamba_d_head"]):
            raise ValueError("mamba_expand x hidden_size is not "
                             "mamba_n_heads x mamba_d_head")
        fields = dict(
            vocab_size=config["vocab_size"],
            layer_types=tuple(
                config["layer_types"][:config["num_hidden_layers"]]),
            hidden_size=config["hidden_size"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            intermediate_size=config["shared_intermediate_size"],
            mamba_heads=config["mamba_n_heads"],
            mamba_head_dim=config["mamba_d_head"],
            mamba_state=config["mamba_d_state"],
            mamba_conv=config["mamba_d_conv"],
            mamba_chunk=config["mamba_chunk_size"],
            embedding_multiplier=float(config["embedding_multiplier"]),
            residual_multiplier=float(config["residual_multiplier"]),
            attention_multiplier=float(config["attention_multiplier"]),
            logits_scaling=float(config["logits_scaling"]),
            rms_eps=float(config["rms_norm_eps"]))
        fields.update(kw)  # attn_impl, dtype, remat, loss_chunk; overrides
        fields["dtype"] = jnp.dtype(fields.get("dtype", cls.dtype))
        return cls(**fields)

    # -- shapes ----------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    def matrix_shapes(self, kind: str) -> dict:
        """{name: (in, out)} of one layer's matrices."""
        d, f, inner = self.hidden_size, self.intermediate_size, self.mamba_inner
        ffn = {"w_in": (d, 2 * f), "w_out": (f, d)}
        if kind == ATTENTION:
            kv = self.num_kv_heads * self.head_dim
            return {"wq": (d, d), "wk": (d, kv), "wv": (d, kv), "wo": (d, d),
                    **ffn}
        return {"in_proj": (d, 2 * inner + 2 * self.mamba_state
                            + self.mamba_heads),
                "out_proj": (inner, d), **ffn}

    def setup(self):
        d = self.hidden_size
        for kind in self.layer_types:
            if kind not in (MAMBA, ATTENTION):
                raise ValueError(f"layer kind {kind!r} is neither "
                                 f"{MAMBA!r} nor {ATTENTION!r}")
        if self.num_heads % self.num_kv_heads or d % self.num_heads:
            raise ValueError("query heads must divide the hidden size, "
                             "K/V heads the query heads")
        matrix = nn.initializers.normal(0.02)

        def layer(kind):
            def init(rng):
                shapes = self.matrix_shapes(kind)
                keys = jax.random.split(rng, len(shapes) + 3)
                out = {name: matrix(k, shape, jnp.float32)
                       for (name, shape), k in zip(shapes.items(), keys)}
                out.update(norm1=jnp.ones((d,), jnp.float32),
                           norm2=jnp.ones((d,), jnp.float32))
                if kind == MAMBA:
                    out.update(self._mamba_vectors(keys[-3:]))
                return out
            return init

        self.embed = self.param("embed", matrix, (self.vocab_size, d),
                                jnp.float32)
        self.layers = [self.param(f"layer_{i}", layer(kind))
                       for i, kind in enumerate(self.layer_types)]
        self.final_norm = self.param("final_norm", nn.initializers.ones, (d,),
                                     jnp.float32)

    def _mamba_vectors(self, keys) -> dict:
        """Mamba-2's published initialisation: dt log-uniform in [0.001,
        0.1] through the inverse softplus into ``dt_bias``, ``A_log`` the
        log of U(1, 16), ``D`` ones; the convolution as torch initialises a
        depthwise ``Conv1d`` (uniform in +-1/sqrt(k))."""
        heads, k = self.mamba_heads, self.mamba_conv
        channels = self.mamba_inner + 2 * self.mamba_state
        dt = jnp.exp(jax.random.uniform(
            keys[0], (heads,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        bound = k ** -0.5
        return {
            "conv_w": jax.random.uniform(keys[2], (k, channels), jnp.float32,
                                         -bound, bound),
            "conv_b": jnp.zeros((channels,), jnp.float32),
            "dt_bias": _inverse_softplus(jnp.maximum(dt, 1e-4)),
            "A_log": jnp.log(jax.random.uniform(
                keys[1], (heads,), jnp.float32, 1.0, 16.0)),
            "D": jnp.ones((heads,), jnp.float32),
            "gate_norm": jnp.ones((self.mamba_inner,), jnp.float32),
        }

    # -- what the estimator writes down once per fit (fit_facts rule) --------
    def fit_facts(self, x) -> dict:
        """``x`` is a sample of the staged feature, [rows, T + 1] ids: a row
        holds T predicted tokens. ``flops_per_row`` is the model's FLOPs of
        a training step on one row, forward + backward = 3 x forward, from
        shapes (``flops_per_row_parts``); recomputation does not count."""
        t = x.shape[1] - 1
        parts = self.flops_per_row_parts(t)
        kept = self._remat_keeps(t)
        return {
            "layer_kinds": ",".join(self.layer_types),
            "layer_kinds.mamba": self.layer_types.count(MAMBA),
            "layer_kinds.attention": self.layer_types.count(ATTENTION),
            "ssd_chunk": min(self.mamba_chunk, t),
            "ssd_flops_per_row": parts["scan"],
            "remat": bool(self.remat), "remat_keeps": ",".join(kept),
            "remat_kept_bytes_per_row": sum(kept.values()),
            **LOSS_FACTS,
            "tokens_per_row": t, "flops_per_row": sum(parts.values())}

    def flops_per_row_parts(self, t: int) -> dict:
        """Model FLOPs of a training step on a row of ``t`` tokens by part:
        ``layers`` (6 x matrix parameters x tokens, the convolution's 2 k a
        channel beside them), ``scan`` (the dual form's four products at
        this chunk size, causal pairs inside a chunk), ``attention`` (causal:
        t (t + 1) / 2 kept pairs), ``head`` (the tied embedding, once)."""
        d, n = self.hidden_size, self.mamba_state
        heads, p = self.mamba_heads, self.mamba_head_dim
        mamba = self.layer_types.count(MAMBA)
        matrices = sum(
            a * b for kind in self.layer_types
            for a, b in self.matrix_shapes(kind).values())
        conv = mamba * self.mamba_conv * (self.mamba_inner + 2 * n)
        q = min(self.mamba_chunk, t)
        pairs = (t // q) * (q * (q + 1) // 2)  # kept (i, j) pairs of a row
        scan = mamba * (2 * n * pairs + 2 * heads * p * pairs
                        + 2 * 2 * heads * p * n * t)
        return {
            "layers": 6 * (matrices + conv) * t,
            "scan": 3 * scan,
            "attention": self.layer_types.count(ATTENTION)
            * 12 * d * (t * (t + 1) // 2),
            "head": 6 * d * self.vocab_size * t}

    def _remat_keeps(self, t: int) -> dict:
        """{name: bytes the layers keep of a row of ``t`` tokens for the
        backward pass}, of ``REMAT_KEEPS``: nothing without ``remat`` (then
        everything is kept), the attention's two only where the flash
        kernel names them."""
        if not self.remat:
            return {}
        wide = t * self.hidden_size * jnp.dtype(self.dtype).itemsize
        attention = self.layer_types.count(ATTENTION)
        sizes = {"attn_out": attention * wide,
                 "attn_lse": attention * 4 * self.num_heads * t,
                 "mlp_out": len(self.layer_types) * wide}
        flash = self.attn_impl in ("flash", "ulysses_flash")
        return {name: sizes[name] for name in REMAT_KEEPS
                if flash or name not in SAVED_RESIDUALS}

    # -- pieces --------------------------------------------------------------
    def _dot(self, x, w):
        return jnp.dot(x, w.astype(self.dtype))

    def _residual(self, h, m):
        # in float32: 0.22 is not a bf16 number
        return (h.astype(jnp.float32)
                + self.residual_multiplier * m.astype(jnp.float32)
                ).astype(h.dtype)

    def _attention(self, w, y):
        with jax.named_scope("hybridlm.attention"):
            b, t, _ = y.shape
            dh, group = self.head_dim, self.num_heads // self.num_kv_heads

            def split(z):  # [B, T, heads x Dh] -> [B, heads, T, Dh]
                return z.reshape(b, t, -1, dh).transpose(0, 2, 1, 3)

            # the attention's own scale is head_dim ** -0.5
            scale = self.attention_multiplier * math.sqrt(dh)
            q = split(self._dot(y, w["wq"])) * jnp.asarray(scale, self.dtype)
            k = jnp.repeat(split(self._dot(y, w["wk"])), group, axis=1)
            v = jnp.repeat(split(self._dot(y, w["wv"])), group, axis=1)
            o = _attend(q, k, v, impl=self.attn_impl, axis="sp", causal=True)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, self.hidden_size)
            return self._dot(o, w["wo"])

    def _conv(self, w, x):
        """Depthwise causal convolution over time and its silu, float32:
        ``out_t = bias + sum_k w[k] x_{t - (K - 1) + k}``."""
        k, t = self.mamba_conv, x.shape[1]
        padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
        out = w["conv_b"] + sum(
            w["conv_w"][i] * padded[:, i:i + t] for i in range(k))
        return nn.silu(out).astype(x.dtype)

    def _mamba(self, w, u):
        with jax.named_scope("hybridlm.mamba"):
            b, t, _ = u.shape
            inner, n = self.mamba_inner, self.mamba_state
            heads, p = self.mamba_heads, self.mamba_head_dim
            z, xbc, dt = jnp.split(self._dot(u, w["in_proj"]),
                                   [inner, 2 * inner + 2 * n], axis=-1)
            x, bm, cm = jnp.split(self._conv(w, xbc), [inner, inner + n],
                                  axis=-1)
            dt = jax.nn.softplus(dt.astype(jnp.float32) + w["dt_bias"])
            y = ssd_chunk_scan(x.reshape(b, t, heads, p), dt,
                               -jnp.exp(w["A_log"]), bm, cm, w["D"],
                               self.mamba_chunk)
            return self._dot(self._gated_norm(w, y.reshape(b, t, inner), z),
                             w["out_proj"])

    def _gated_norm(self, w, y, z):
        """RMSNorm(y silu(z)): the gate BEFORE the norm (Mamba-2's order),
        the norm over the whole inner width (one group)."""
        gated = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        return rms_norm(gated.astype(self.dtype), w["gate_norm"], self.rms_eps)

    def _mlp(self, w, y):
        with jax.named_scope("hybridlm.mlp"):
            g, u = jnp.split(self._dot(y, w["w_in"]), 2, axis=-1)
            return checkpoint_name(self._dot(nn.silu(g) * u, w["w_out"]),
                                   "mlp_out")

    def _block(self, kind, w, h):
        mixer = self._mamba if kind == MAMBA else self._attention
        h = self._residual(h, mixer(w, rms_norm(h, w["norm1"], self.rms_eps)))
        return self._residual(
            h, self._mlp(w, rms_norm(h, w["norm2"], self.rms_eps)))

    def head(self, h):
        """Logits, float32, from the final norm's output (the tied head)."""
        return jnp.dot(h, self.embed.T.astype(self.dtype),
                       preferred_element_type=jnp.float32
                       ) / self.logits_scaling

    # -- surfaces ------------------------------------------------------------
    def hidden_states(self, tokens):
        """The final norm's output [B, T, D]: what the head reads."""
        h = (self.embedding_multiplier * self.embed[tokens]).astype(self.dtype)
        block = jax.checkpoint(
            self._block, static_argnums=(0,),
            policy=jax.checkpoint_policies.save_only_these_names(*REMAT_KEEPS),
        ) if self.remat else self._block
        for kind, w in zip(self.layer_types, self.layers):
            h = block(kind, w, h)
        return rms_norm(h, self.final_norm, self.rms_eps)

    def __call__(self, tokens):
        """Logits [B, T, V]."""
        return self.head(self.hidden_states(tokens))

    def loss(self, x, y=None, with_states=False):
        """Mean next-token cross-entropy on ``x`` int32 [B, T+1] (inputs
        ``x[:, :-1]``, targets ``x[:, 1:]``; ``y`` is not used). Returns
        ``(loss, aux)``; ``with_states`` puts ``hidden`` [B, T, D], the
        state the head read, into ``aux`` (for a comparison of the logits;
        not for a fit, whose evaluation would average it)."""
        h = self.hidden_states(x[:, :-1])
        loss, _ = chunked_cross_entropy(
            h, self.embed, 1, x[:, 1:], self.loss_chunk, "hybridlm.loss",
            scale=1.0 / self.logits_scaling)
        return loss, {"hidden": h} if with_states else {}


def hybridlm_optimizer(learning_rate: float = 3e-4, b1: float = 0.9,
                       b2: float = 0.95, weight_decay: float = 0.1):
    """AdamW as LM pre-training runs it: decay on the parameters with two or
    more axes only (the matrices, the embedding, the convolution's taps;
    not on norm gains, ``A_log``, ``D``, ``dt_bias`` or the convolution's
    bias), float32 moments, no schedule."""
    return looplm_optimizer(learning_rate, b1, b2, weight_decay)
