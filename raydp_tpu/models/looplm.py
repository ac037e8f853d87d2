"""Looped decoder LM (Ouro / LoopLM, arXiv:2510.25741): one stack of ``L``
layers applied ``R`` times with ONE set of parameters, an exit (the shared
head) and an exit gate after every loop step, and a loss over all exits.

::

    h = E[x]
    for t in 1..R:                       # the SAME L layers every t
      for l in 1..L:
        a = h + RMSNorm_l2(Attn_l(RMSNorm_l1(h)))        # sandwich norm
        h = a + RMSNorm_l4(MLP_l (RMSNorm_l3(a)))
      h = RMSNorm_f(h)                   # closes every loop step: feeds exit t AND step t+1
      z_t = h W_head ;  lam_t = sigmoid(h w_g + b_g)
    p_t = lam_t prod_{j<t}(1 - lam_j)  (t < R);  p_R = prod_{j<R}(1 - lam_j)
    loss = mean_tokens[sum_t p_t CE(z_t, next)] - beta mean_tokens[H(p)]

The parameter tree holds L layers, not R x L: sharing is by construction, and
a weight's gradient sums over its R uses. Parameters are float32; ``dtype``
is the compute dtype (bf16 on the chip): matmul operands and the residual
stream; norms' statistics, RoPE, the gate, the logits and the loss are
float32. Attention is the repo's ``_attend`` (``attn_impl="flash"`` on the
chip), shared with ``models/transformer.py``.

The estimator trains it with ``loss="model"``: ``loss(x)`` takes the int32
``[B, T+1]`` sequence column whole, reads ``x[:, :-1]`` and predicts
``x[:, 1:]``. The four exits' loss is ONE call of ``chunked_cross_entropy``
after the loop, over the R x B x T closing states: each chunk of
``loss_chunk`` tokens computes its logits once, and under differentiation
takes their gradient there and then (three products a chunk: logits, the
gradient back to the state, the head's gradient; none in the backward
pass). What the loss holds: one chunk of float32 logits, the gradient back
to the states (their shape and dtype) and one float32 accumulator of the
head's shape; never a second chunk of logits, nothing recomputed. Blocks
are recomputed from their inputs (``remat``) but for ``REMAT_KEEPS``:
the flash kernel's output and log-sum-exp and ``w_down``'s output are kept
from the forward pass (at the published widths, bf16, T 4096: 16.8 MB + 0.26
MB + 16.8 MB a row and block application, 811.6 MB a row over the 24), so
the backward pass runs no flash forward and no down-projection a second
time; q, k, v, the norms, RoPE, ``wo``, gate and up are rebuilt.
``fit_facts`` says what is kept (``remat_keeps``,
``remat_kept_bytes_per_row``) and what the loss does (``LOSS_FACTS``).
"""

from __future__ import annotations

import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from raydp_tpu import obs
from raydp_tpu.models.transformer import _attend, attention_backward_facts
from raydp_tpu.ops.flash_attention import SAVED_RESIDUALS

LAYER_MATRICES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
LAYER_NORMS = ("norm1", "norm2", "norm3", "norm4")
# what a recomputed block keeps from its forward pass, the values that cost
# most to rebuild per byte kept: the flash kernel's output and log-sum-exp
# (with both kept the recomputed kernel call is dead code) and ``w_down``'s
# output
REMAT_KEEPS = SAVED_RESIDUALS + ("mlp_out",)
# what ``chunked_cross_entropy`` does, for ``fit_facts``: the loss's gradient
# is taken in the forward sweep, with three products over the vocabulary a
# chunk (logits, the gradient back to the state, the head's gradient)
LOSS_FACTS = {"loss_grad": "forward", "loss_products_per_chunk": 3}


def rms_norm(x, gain, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * gain).astype(x.dtype)


def rope_tables(t: int, head_dim: int, theta: float):
    """cos, sin [T, head_dim/2] in float32 (rotate-half over the whole head)."""
    inv = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    angles = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x, cos, sin):
    """x [B, H, T, D]: out = x * cos + rotate_half(x) * sin, in float32."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def exit_mass(lam):
    """Exit distribution p [R, ...] from the gates lam [R, ...] (the last
    gate is not used: the last exit takes what is left)."""
    survive, mass = jnp.ones_like(lam[0]), []
    for gate in lam[:-1]:
        mass.append(gate * survive)
        survive = survive * (1.0 - gate)
    return jnp.stack(mass + [survive])


def _chunk_ce(h_c, y_c, w, contract, scale):
    """One chunk: its cross-entropy [c], its float32 logits ``scale * h_c .
    w`` [c, V] with their log-sum-exp [c], and where the targets lie in them
    (bool [c, V]); ``contract`` is the axis of ``w`` that meets ``h_c``'s
    features."""
    # the chunk as a buffer of its own: with its slice fused into the
    # products, they read a stack of states too large for the chip's fast
    # memory (the four exits', 134 MB) from HBM tile by tile, and the head's
    # gradient product took 58.8 ms a step where 40.5 is its time (PR 32)
    h_c = lax.optimization_barrier(h_c)
    z = scale * lax.dot_general(h_c, w, (((1,), (contract,)), ((), ())),
                                preferred_element_type=jnp.float32)
    lse = jax.nn.logsumexp(z, axis=-1)
    hot = y_c[:, None] == lax.broadcasted_iota(jnp.int32, z.shape, 1)
    return lse - jnp.sum(jnp.where(hot, z, 0.0), axis=-1), z, lse, hot


def _chunks(chunk, *flat):
    """``flat`` arrays [n, ...] as [n / chunk, chunk, ...]; one chunk of the
    whole where ``chunk`` is 0, not smaller or does not divide ``n``."""
    n = flat[0].shape[0]
    size = chunk if chunk and chunk < n and not n % chunk else n
    return tuple(a.reshape(n // size, size, *a.shape[1:]) for a in flat)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _head_cross_entropy(h, w, targets, weight, contract, scale, chunk):
    """(sum of ``weight`` x cross-entropy, per-token cross-entropy [n]) of
    the float32 logits ``scale * h . w`` of ``h`` [n, D] against ``targets``
    [n], a chunk of logits at a time. Under differentiation the forward
    sweep takes the gradient too (``_head_cross_entropy_fwd``)."""
    cast = w.astype(h.dtype)
    ce = lax.map(lambda hy: _chunk_ce(*hy, cast, contract, scale)[0],
                 _chunks(chunk, h, targets)).reshape(-1)
    return jnp.sum(weight * ce), ce


def _head_cross_entropy_fwd(h, w, targets, weight, contract, scale, chunk):
    """Each chunk once: its logits, its cross-entropy, the gradient of
    ``weight . ce`` with respect to the logits (float32, the operand dtype
    autodiff's two backward products take it in), and from it the gradient
    back to ``h`` (kept, ``h``'s dtype) and to ``w`` (summed over chunks in
    ONE float32 accumulator of ``w``'s own layout). No chunk's logits
    outlive the chunk."""
    cast = w.astype(h.dtype)
    vocab = 1 - contract

    def body(dw, chunk_of):
        h_c, y_c, weight_c = chunk_of
        ce, z, lse, hot = _chunk_ce(h_c, y_c, cast, contract, scale)
        softmax = jnp.exp(z - lse[:, None])
        dz = jnp.where(hot, softmax - 1.0, softmax) * (
            weight_c * scale)[:, None]
        dh = lax.dot_general(dz, cast, (((1,), (vocab,)), ((), ())),
                             preferred_element_type=jnp.float32)
        # in the weight's own layout: [D, V] = h^T dz, [V, D] = dz^T h
        pair = (h_c, dz) if vocab else (dz, h_c)
        dw = dw + lax.dot_general(*pair, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return dw, (ce, dh.astype(h.dtype))

    dw, (ce, dh) = lax.scan(body, jnp.zeros(w.shape, jnp.float32),
                            _chunks(chunk, h, targets, weight))
    ce = ce.reshape(-1)
    return (jnp.sum(weight * ce), ce), (dh.reshape(h.shape), dw, ce)


def _head_cross_entropy_bwd(contract, scale, chunk, kept, cotangents):
    (dh, dw, ce), (g, _) = kept, cotangents  # ``ce`` is for reporting only
    return (g * dh).astype(dh.dtype), g * dw, None, g * ce


_head_cross_entropy.defvjp(_head_cross_entropy_fwd, _head_cross_entropy_bwd)


def chunked_cross_entropy(h, w, contract: int, targets, chunk: int,
                          scope: str, scale: float = 1.0, weight=None):
    """The head's product and the cross-entropy in one: ``(sum over tokens
    of weight x ce, ce)`` of the float32 logits ``scale * h . w`` against
    ``targets``, ``chunk`` tokens of logits at a time (0, a chunk of the
    whole or one that does not divide it: all at once). ``h`` is [..., D],
    ``targets`` and ``weight`` its leading shape (no ``weight``: the mean),
    ``w`` the float32 head, cast to ``h``'s dtype here, whose axis
    ``contract`` meets D (0 for [D, V], 1 for a tied embedding's [V, D]).
    ``ce`` is for reporting and carries no gradient.

    Its backward pass is its own: under differentiation every chunk's
    logits are computed ONCE, in the forward sweep, and their gradient is
    taken there: three products a chunk (logits, the gradient back to
    ``h``, the weight's gradient), none in the backward sweep, which scales
    what was kept by the cotangent. Kept: the gradient back to ``h`` (its
    shape and dtype) and one float32 accumulator of ``w``'s shape; never a
    chunk of logits. Without differentiation: the chunked forward alone."""
    n = targets.size
    if weight is None:
        weight = jnp.full(targets.shape, 1.0 / n, jnp.float32)
    with obs.device_scope(scope):
        total, ce = _head_cross_entropy(
            h.reshape(n, h.shape[-1]), w, targets.reshape(n),
            weight.reshape(n), contract, scale, chunk)
    return total, lax.stop_gradient(ce).reshape(targets.shape)


class LoopLM(nn.Module):
    vocab_size: int
    hidden_size: int = 2048
    num_heads: int = 16
    num_layers: int = 6
    intermediate_size: int = 5632
    loop_steps: int = 4
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    entropy_beta: float = 0.1
    attn_impl: str = "full"  # "flash" on the chip; as TransformerLM's
    dtype: Any = jnp.bfloat16  # compute dtype; parameters are float32
    remat: bool = True  # recompute each block in the backward pass
    loss_chunk: int = 2048  # tokens of the exits' logits held at a time; 0: all

    def setup(self):
        d, f, v = self.hidden_size, self.intermediate_size, self.vocab_size
        matrix = nn.initializers.normal(0.02)
        shapes = {"wq": (d, d), "wk": (d, d), "wv": (d, d), "wo": (d, d),
                  "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)}

        def layer(rng):
            keys = jax.random.split(rng, len(LAYER_MATRICES))
            out = {name: matrix(k, shapes[name], jnp.float32)
                   for name, k in zip(LAYER_MATRICES, keys)}
            out.update({name: jnp.ones((d,), jnp.float32) for name in LAYER_NORMS})
            return out

        def gate(rng):
            return {"w": matrix(rng, (d,), jnp.float32),
                    "b": jnp.zeros((), jnp.float32)}

        self.embed = self.param("embed", matrix, (v, d), jnp.float32)
        # ONE tree of L layers, whatever the loop count
        self.layers = [self.param(f"layer_{i}", layer)
                       for i in range(self.num_layers)]
        self.final_norm = self.param("final_norm", nn.initializers.ones, (d,),
                                     jnp.float32)
        self.head_w = self.param("head", matrix, (d, v), jnp.float32)
        self.gate = self.param("gate", gate)

    # -- what the estimator writes down once per fit (fit_facts rule) --------
    def fit_facts(self, x) -> dict:
        """``x`` is a sample of the staged feature, [rows, T + 1] ids: a row
        holds T predicted tokens. ``flops_per_row`` is the model's FLOPs of
        a training step on one row, forward + backward = 3 x forward, from
        shapes: every layer counted ``loop_steps`` times, one head per
        exit, causal attention (T (T + 1) / 2 kept pairs); recomputation
        does not count. XLA's count of the step program cannot stand in: it
        counts a scanned loop's body once and no Mosaic call.
        ``attention_backward`` and its two numbers: the form the blocks'
        attention backward takes; ``attention_grid`` and
        ``attention.causal_grid_live_share``: the grid its calls step over
        (``transformer.attention_backward_facts``)."""
        t = x.shape[1] - 1
        d, applications = self.hidden_size, self.loop_steps * self.num_layers
        layer = 4 * d * d + 3 * d * self.intermediate_size
        flops = (6 * layer * t * applications
                 + 6 * d * self.vocab_size * t * self.loop_steps
                 + 12 * d * (t * (t + 1) // 2) * applications)
        kept = self._remat_keeps(t)
        return {"loop_steps": self.loop_steps,
                "layer_applications_per_step": applications,
                "loop": "scan", "remat": bool(self.remat),
                "remat_keeps": ",".join(kept),
                "remat_kept_bytes_per_row": applications * sum(kept.values()),
                **attention_backward_facts(
                    self.attn_impl, t, d // self.num_heads, self.dtype,
                    {"global": applications}),
                **LOSS_FACTS, "tokens_per_row": t, "flops_per_row": flops}

    def _remat_keeps(self, t: int) -> dict:
        """{name: bytes one block application keeps of a row of ``t`` tokens
        for the backward pass}, of ``REMAT_KEEPS``: nothing without
        ``remat`` (then everything is kept), and the attention's two only
        where the flash kernel names them."""
        if not self.remat:
            return {}
        wide = t * self.hidden_size * jnp.dtype(self.dtype).itemsize
        sizes = {"attn_out": wide, "attn_lse": 4 * self.num_heads * t,
                 "mlp_out": wide}
        flash = self.attn_impl in ("flash", "ulysses_flash")
        return {name: sizes[name] for name in REMAT_KEEPS
                if flash or name not in SAVED_RESIDUALS}

    # -- pieces --------------------------------------------------------------
    def _dot(self, x, w):
        return jnp.dot(x, w.astype(self.dtype))

    def _block(self, w, h, cos, sin):
        with obs.device_scope("looplm.block"):
            b, t, d = h.shape
            heads, eps = self.num_heads, self.rms_eps

            def split(z):  # [B, T, D] -> [B, H, T, Dh]
                return z.reshape(b, t, heads, d // heads).transpose(0, 2, 1, 3)

            y = rms_norm(h, w["norm1"], eps)
            q = apply_rope(split(self._dot(y, w["wq"])), cos, sin)
            k = apply_rope(split(self._dot(y, w["wk"])), cos, sin)
            v = split(self._dot(y, w["wv"]))
            o = _attend(q, k, v, impl=self.attn_impl, axis="sp", causal=True)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, d)
            a = h + rms_norm(self._dot(o, w["wo"]), w["norm2"], eps)
            y = rms_norm(a, w["norm3"], eps)
            m = checkpoint_name(self._dot(
                nn.silu(self._dot(y, w["w_gate"])) * self._dot(y, w["w_up"]),
                w["w_down"]), "mlp_out")
            return a + rms_norm(m, w["norm4"], eps)

    def _loop_step(self, h, cos, sin):
        """The L layers once, then the final norm: the state that feeds this
        step's exit, its gate and the next step."""
        block = jax.checkpoint(
            self._block,
            policy=jax.checkpoint_policies.save_only_these_names(*REMAT_KEEPS),
        ) if self.remat else self._block
        for w in self.layers:
            h = block(w, h, cos, sin)
        return rms_norm(h, self.final_norm, self.rms_eps)

    def _gate(self, h):
        z = jnp.einsum("btd,d->bt", h.astype(jnp.float32), self.gate["w"])
        return jax.nn.sigmoid(z + self.gate["b"])

    def head(self, h):
        """One exit's logits, float32, from a loop step's closing state."""
        return jnp.dot(h, self.head_w.astype(self.dtype),
                       preferred_element_type=jnp.float32)

    def _embed(self, tokens):
        cos, sin = rope_tables(tokens.shape[1],
                               self.hidden_size // self.num_heads,
                               self.rope_theta)
        return self.embed[tokens].astype(self.dtype), cos, sin

    # -- surfaces ------------------------------------------------------------
    def hidden_states(self, tokens):
        """(h [R, B, T, D], lam [R, B, T]): every loop step's closing state
        and gate (the loop as a Python ``for``: small sizes, tests, checks)."""
        h, cos, sin = self._embed(tokens)
        hs, lams = [], []
        for _ in range(self.loop_steps):
            h = self._loop_step(h, cos, sin)
            hs.append(h)
            lams.append(self._gate(h))
        return jnp.stack(hs), jnp.stack(lams)

    def exits(self, tokens):
        """(logits [R, B, T, V], lam [R, B, T]): every exit whole."""
        hs, lam = self.hidden_states(tokens)
        return jnp.stack([self.head(h) for h in hs]), lam

    def __call__(self, tokens):
        """The last exit's logits [B, T, V]."""
        hs, _ = self.hidden_states(tokens)
        return self.head(hs[-1])

    def loss(self, x, y=None, with_states=False):
        """The training objective on ``x`` int32 [B, T+1] (inputs
        ``x[:, :-1]``, targets ``x[:, 1:]``; ``y`` is not used). Returns
        ``(loss, {"exit_loss": [R], "exit_mass": [R]})``: each exit's mean
        cross-entropy and mean mass, which the estimator's evaluation
        reports. ``with_states`` adds what the scanned loop itself held:
        ``hidden`` [R, B, T, D], every loop step's closing state, and
        ``mass`` [R, B, T], the exit distribution (for a comparison exit by
        exit; not for a fit, whose evaluation would average them)."""
        tokens, targets = x[:, :-1], x[:, 1:]
        h, cos, sin = self._embed(tokens)

        def step(h, _):
            h = self._loop_step(h, cos, sin)
            return h, (h, self._gate(h))

        with obs.device_scope("looplm.loop"):
            _, (hidden, lam) = lax.scan(step, h, None,
                                         length=self.loop_steps)
        # every exit's loss in ONE call, after the loop: its weight's
        # gradient is then one accumulator, not one a loop step
        p = exit_mass(lam)
        weighted, ce = chunked_cross_entropy(
            hidden, self.head_w, 0, jnp.broadcast_to(targets, p.shape),
            self.loss_chunk, "looplm.exit_loss", weight=p / targets.size)
        entropy = -jnp.sum(jnp.mean(
            p * jnp.log(jnp.maximum(p, 1e-30)), axis=(1, 2)))
        aux = {"exit_loss": jnp.mean(ce, axis=(1, 2)),
               "exit_mass": jnp.mean(p, axis=(1, 2))}
        if with_states:
            aux.update(hidden=hidden, mass=p)
        return weighted - self.entropy_beta * entropy, aux


def looplm_optimizer(learning_rate: float = 3e-4, b1: float = 0.9,
                     b2: float = 0.95, weight_decay: float = 0.1):
    """AdamW as LM pre-training runs it: decay on the parameters with two or
    more axes only (not on norm gains or the gate), float32 moments, no
    schedule."""
    import optax

    return optax.adamw(
        learning_rate, b1=b1, b2=b2, weight_decay=weight_decay,
        mask=lambda params: jax.tree.map(lambda a: a.ndim >= 2, params))
