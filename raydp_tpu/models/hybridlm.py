"""Hybrid decoder LM: a MIXER KIND (``mamba``, ``attention``, ``conv``,
``delta``, ``kda``, ``mla``) and an FFN KIND (``dense``, ``experts``) per
layer, in the order ``layer_types`` and ``ffn_types`` give. Six published
families are built from
their ``config.json`` (``from_config`` reads ``model_type``): ``granitemoehybrid``
with no experts (Mamba-2 and grouped-query attention without positions, a
dense SwiGLU after each, four multipliers), ``lfm2_moe`` (gated short
convolutions and grouped-query attention with RoPE and per-head q/k norms;
leading dense SwiGLUs, then routed experts of which this chip holds a share)
and ``smallthinker`` (every layer grouped-query attention with an explicit
``head_dim`` and routed ReGLU experts: per layer a window and RoPE, or
neither, as ``sliding_window_layout`` and ``rope_layout`` say; the router is
fed from the block's INPUT, before the first norm, and takes a softmax over
the experts it selected; an untied head) and ``olmo_hybrid`` (gated
delta-rule linear-attention layers, ``linear_attention`` in its
``layer_types``, beside full-attention layers without positions whose q/k
norms run over the whole projection; a dense SwiGLU in every layer; a
sub-layer's OUTPUT is normed; this chip holds a share of every mixer's HEADS;
an untied head) and ``bailing_hybrid`` (``layer_group_size`` - 1 ``kda``
layers to one ``mla`` layer, both under a head-wise sigmoid output gate;
leading dense SwiGLUs, then routed experts under a GROUP-LIMITED selection
beside a SHARED expert every token passes; an untied head) and
``glm4_moe_lite`` (DeepSeek-V3's block: ``mla`` in EVERY layer with a
LOW-RANK QUERY and no output gate, one leading dense SwiGLU, then routed
experts beside a shared one, no group limit; an untied head; and one
MULTI-TOKEN-PREDICTION module whose loss counts, below).

::

    h = embedding_multiplier E[x]
    for mixer, ffn in zip(layer_types, ffn_types):
      h = h + residual_multiplier Mixer_mixer(RMSNorm(h))     # norm_placement
      h = h + residual_multiplier FFN_ffn(RMSNorm(h))         # "pre"; "post":
      # h = h + residual_multiplier RMSNorm(Mixer_mixer(h)), the FFN alike
    logits = RMSNorm_f(h) E^T / logits_scaling               # tied head; or
                                                             # W_head, untied
    loss = mean next-token cross-entropy

``norm_placement`` is a field of the family: ``"pre"`` norms a sub-layer's
input (the first three families), ``"post"`` its output before the residual
addition (``olmo_hybrid``: the sub-layer reads the stream as it is; the gains
are ``norm1`` and ``norm2`` either way).

``dense``: ``[g, u] = W_in y; W_out(silu(g) u)``. ``experts``
(``ops.experts.routed_experts``, which says how, and the second scoring
rule, ``expert_scoring="softmax"``: the ``experts_per_token`` largest logits
and a softmax over them, no bias; ``expert_activation``: ``silu`` or
``relu``; ``router_input="block"``: the router reads the block's input
``h`` in ``y``'s place): sigmoid scores over ALL
``experts_total`` experts, the ``experts_per_token`` largest of score +
``expert_bias`` (the bias enters the selection only; what the layer hands
back as its gradient is each expert's excess load, of which
``hybridlm_optimizer`` makes the balancing rule's step), the selected scores
normalised; the experts ``first_expert ..
first_expert + experts_held - 1`` are here and their part of the result is
computed, for every pair routed to them, however uneven the load; what the
absent experts would add is left out (one chip's share of an
expert-parallel group, without its exchange). The layer cuts its rows'
buffer to the load it sees (``ops.experts``: the likely bound, with the
worst case, tokens x ``experts_per_token`` rows, as the path of an
overflow). What the expert layers report of a step (``train_report``: each
held expert's load; the pairs past the buffer's bound: zero, an overflow
runs the worst case; the layers that did run it) the estimator sums over an
epoch's steps and hands to ``epoch_facts``.

``conv`` (a gated short convolution)::

    [B | C | x] = W_in u;  y = W_out( C * conv1d_causal(B * x) )

depthwise over time, ``conv_kernel`` taps, no bias, float32.

``attention``: q of ``num_heads`` heads of ``head_dim`` (the hidden size
over the heads unless given), k and v of ``num_kv_heads`` (each
serves ``num_heads / num_kv_heads`` consecutive query heads), no bias;
``qk_norm``: an RMSNorm over each head of q and of k (gains [head_dim]; with
``qk_norm_over="projection"`` over all the heads HELD here at once, gains
[heads x head_dim], before the split into heads);
``rope_theta`` > 0: RoPE (``looplm``'s rotate-half) on q and k, else no
positions (``rope_layers``, a flag a layer, takes RoPE away from the layers
it marks 0); causal softmax of ``attention_multiplier q.k``, and in a layer
whose entry of ``attention_windows`` is W > 0 over the query's own position
and the W - 1 before it only (scope ``hybridlm.attention.window``, the
others ``hybridlm.attention.global``; the flash kernels' grid follows the
window). It runs through
the repo's ``_attend`` (``attn_impl="flash"`` on the chip): q is multiplied
by ``attention_multiplier sqrt(head_dim)`` beforehand, so that the kernels'
own ``head_dim ** -0.5`` gives the multiplier, and K and V are repeated to
the query heads in HBM.

PLACEMENT (``placed_by_load``). The softmax-over-the-selected rule takes no
bias, so no rule evens its load, and which experts sit on this chip is what
a group has left to choose: from the load the seeded routers give on the
training batches, layer after layer, ``ops.experts.place`` deals each
layer's experts to the group's chips and the router's columns are re-ordered
so that this chip's slots score the experts placed here
(``expert_placement``, read by ``init`` alone: the experts' own weights are
seeded alike). ``embed_std`` is the embedding's seeded spread; the matrices'
is 0.02.

``delta`` (a gated delta rule; H heads HELD here of ``delta_heads_total``,
keys of Dk and values of Dv, a state [Dv, Dk] a head)::

    q^, k^, v^ = silu(conv1d_causal(W_q a)), silu(conv(W_k a)), silu(conv(W_v a))
    q_t = l2norm_head(q^_t) / sqrt(Dk);  k_t = l2norm_head(k^_t);  v_t = v^_t
    beta_t = 2 sigmoid(W_b a)_t                               # beta in (0, 2)
    alpha_t = exp(-exp(A_log) softplus((W_a a)_t + dt_bias))
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T;  o_t = S_t q_t
    out = W_o [ RMSNorm_head(o_t; gate_norm [Dv]) * silu(W_g a)_t ]

depthwise, ``delta_conv`` taps, no bias; the recurrence is
``ops.delta_rule.gated_delta_rule`` (chunks of ``ops.delta_rule.CHUNK``
tokens: products and one unit-lower-triangular solve a chunk, a serial
recurrence over the chunk states; scope ``delta_rule`` inside
``hybridlm.delta``); convolution, l2 norms, beta, decays, the triangular
inverse, the state and the read-out's norm are float32. A share of the heads
is the columns of W_q, W_k, W_v, W_g, W_a, W_b, the convolution's channels,
``A_log`` and ``dt_bias`` and the rows of W_o that belong to them: what the
other heads would add through their rows of W_o is left out (one chip of a
group that divides the heads, without its all-reduce).

``kda`` (Kimi Delta Attention, arXiv:2510.26692: the delta rule with a decay
A CHANNEL of the key; H heads, keys and values of ``delta_key_dim`` /
``delta_value_dim``)::

    q, k, v as ``delta``'s (convolution, silu, l2 norms, q / sqrt(Dk))
    beta_t = sigmoid(W_b a)_t                                 # beta in (0, 1)
    log alpha_t = kda_decay_floor sigmoid(exp(A_log) ((W_f a)_t + dt_bias))
                                          # a VECTOR [Dk] a head, in (floor, 0)
    S_t = S_{t-1} Diag(alpha_t) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T
    out = W_o [ RMSNorm_head(o_t; gate_norm [Dv]) * sigmoid(W_g a)_t[head] ]

``A_log`` a head, ``dt_bias`` a channel, seeded as ``delta``'s; the recurrence
is ``ops.delta_rule.channel_gated_delta_rule`` (one Pallas kernel forward and
one backward, reading and writing [T, H * 128] as the mixer holds it) under
the same scopes (``hybridlm.delta`` around ``delta_rule``). No positions.

``mla`` (latent attention, DeepSeek-V2, arXiv:2405.04434, its TRAINING side:
multi-head attention whose keys are wider than its values; the cache's
latent form is the serving side and is not built)::

    q = W_q a = [q_nope | q_rope] a head          (head_dim = nope + rope_head_dim)
      # ``query_rank`` > 0: q = W_qb RMSNorm(W_qa a; q_norm), a low-rank query
    [c | k_rope] = W_kva a                        (latent_rank | rope_head_dim)
    [k_nope | v] = W_kvb RMSNorm(c; kv_norm)      (nope | value_head_dim a head)
    k = [k_nope | RoPE(k_rope)], the ONE k_rope shared by all the heads;
    q = [q_nope | RoPE(q_rope)]; interleaved pairs (the program turns the
    pairs apart and rotates halves: q.k is the same)
    out = W_o [ softmax_causal(q.k / sqrt(head_dim)) v * sigmoid(W_g a)[head] ]

(``latent_gate`` False: no gate, ``out = W_o [...]`` as it is); scope
``hybridlm.attention`` with ``.query`` around the query's products and
``.latent`` around the two latent products; the flash kernels take v, o and
do at ``value_head_dim``.

MULTI-TOKEN PREDICTION (``mtp_weight`` > 0: one module; DeepSeek-V3,
arXiv:2412.19437 section 2.2, depth 1), on a row ``t_0 .. t_T``::

    u_i = W_eh [ RMSNorm(E[t_{i+1}]; enorm) ; RMSNorm(h_L,i; hnorm) ]
    g   = Block_mtp(u)                   # one more layer of the LAST layer's kinds
    L   = L_main + mtp_weight x mean_{i <= T-2} CE(RMSNorm(g_i; final_norm) W_head, t_{i+2})

``E`` and ``W_head`` are the MAIN model's (their gradients are the sum of
both uses), ``h_L`` the stream the final norm READS, the block's positions
the main model's; every array keeps its T rows and row T - 1, which has no
target, weighs 0. The module's leaves are ``mtp_0``: the block's own, with
``eh_proj`` [2 D, D], ``enorm``, ``hnorm`` and ``final_norm`` beside them;
its router's ``expert_bias`` keeps that name, so ``hybridlm_optimizer``'s
balancing rule moves it. Scopes: ``hybridlm.mtp`` around all of it,
``.mtp.combine`` (two norms, the gather, ``eh_proj``), ``.mtp.loss``; the
block under the scopes every block has. ``mtp_weight`` 0 builds nothing.

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

import contextlib
import functools
import logging
import math
from typing import Any, Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from raydp_tpu import obs
from raydp_tpu.models.looplm import (
    LOSS_FACTS, apply_rope, chunked_cross_entropy, looplm_optimizer, rms_norm,
    rope_tables)
from raydp_tpu.models.transformer import _attend, attention_backward_facts
from raydp_tpu.ops import experts as experts_op
from raydp_tpu.ops import delta_rule
from raydp_tpu.ops import kda_mixer
from raydp_tpu.ops.flash_attention import SAVED_RESIDUALS
from raydp_tpu.ops.ssd import ssd_chunk_scan

MAMBA, ATTENTION, CONV, DELTA = "mamba", "attention", "conv", "delta"
KDA, MLA = "kda", "mla"
DENSE, EXPERTS = "dense", "experts"
# what a recomputed block keeps from its forward pass: the flash kernel's
# output and log-sum-exp (an attention layer; with both kept the recomputed
# kernel call is dead code) and ``w_out``'s output (every layer). A Mamba
# mixer keeps nothing: the scan's backward pass needs the decays, the
# scores and the chunk states, not ``y``, so a kept ``ssd_out`` would spare
# two of its five products for 67 MB a layer and row; a delta-rule mixer
# keeps nothing for the same reason (its scan's result is named
# ``ops.delta_rule.SAVED_OUTPUT`` for a policy that would)
REMAT_KEEPS = SAVED_RESIDUALS + ("mlp_out",)
# an expert layer keeps ``mlp_out`` (its combined result) as every layer
# does, and its discrete part (``ops.experts.KEPT``: every token's choice,
# the sort's permutation and its inverse, the held pairs in token order:
# five int32 vectors of tokens x k and one of tokens, 2.8 MB a layer at
# 32,768 tokens): the backward pass must not choose again (a near-tie could
# fall the other way) and does not sort again; the first
# grouped product's output (294 MB a layer at the likely bound of 40,960
# rows, 940 MB at the worst case's 131,072) is recomputed
EXPERT_KEEPS = (experts_op.KEPT,)
# a ``kda`` layer keeps its scan's result (67 MB a layer at 8192 tokens x 32
# heads of 128): the scan's backward kernel keeps nothing of the forward
# call but its operands (``ops.delta_rule``), so with ``o`` kept the block's
# recomputation has no use for a second forward call and the scan runs once
# a step, not twice; where the mixer's chain around the scan runs as
# ``ops.kda_mixer``'s kernels it keeps their results too (the scan's operands:
# q, k, v at 67 MB each and the float32 log-decay at 134; ``W_o``'s input at
# 67), so that the recomputed block needs no second call of either forward
# kernel: 470 MB a layer in all, and the element-wise passes run twice a step
# and not three times (the plain chain names nothing, and keeps ``o`` alone)
KDA_KEEPS = (delta_rule.SAVED_OUTPUT, kda_mixer.OPERANDS, kda_mixer.READ_OUT)


def _inverse_softplus(x):
    return x + jnp.log(-jnp.expm1(-x))


def _depthwise_causal(x, taps):
    """``out_t = sum_k taps[k] x_{t - (K - 1) + k}`` over time, a channel at
    a time: ``x`` [B, T, C] float32, ``taps`` [K, C]."""
    k, t = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(taps[i] * padded[:, i:i + t] for i in range(k))


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
    # -- what the second family adds; the defaults build the first ----------
    ffn_types: Sequence[str] = ()  # a kind a layer; (): every layer dense
    rope_theta: float = 0.0  # 0: no positions
    qk_norm: bool = False  # RMSNorm over each head of q and k
    conv_kernel: int = 3  # taps of the gated short convolution
    expert_width: int = 0
    experts_total: int = 0  # the router's width
    experts_held: int = 0  # first_expert .. first_expert + experts_held - 1
    first_expert: int = 0
    experts_per_token: int = 0
    routed_scaling: float = 1.0
    expert_bias_spread: float = 0.0  # expert_bias ~ U(+-spread); 0: zeros
    # -- what the third family adds; the defaults build the first two --------
    head_dim: Any = None  # None: hidden_size // num_heads
    attention_windows: Sequence[int] = ()  # keys a layer sees; 0: all. (): 0s
    rope_layers: Sequence[int] = ()  # 1: the layer carries RoPE. (): all do
    expert_scoring: str = "sigmoid"  # or "softmax": over the selected, no bias
    expert_activation: str = "silu"  # or "relu"
    router_input: str = "ffn"  # or "block": the block's input, before norm1
    tied_head: bool = True  # False: a head [D, V] of its own
    embed_std: float = 0.02  # the embedding's seeded spread (matrices: 0.02)
    # an expert layer's seeded router columns in the order the group PLACED
    # its experts (``placed_by_load``); (): as seeded. Read by ``init`` alone
    expert_placement: Sequence[Sequence[int]] = ()
    # -- what the fourth family adds; the defaults build the first three -----
    delta_heads: int = 0  # delta-rule heads HELD here (key heads = value heads)
    delta_heads_total: int = 0  # of so many the layer has; 0: all are held
    delta_key_dim: int = 0
    delta_value_dim: int = 0
    delta_conv: int = 4  # taps of the depthwise convolution on q, k and v
    norm_placement: str = "pre"  # or "post": a sub-layer's OUTPUT is normed
    qk_norm_over: str = "head"  # or "projection": all the held heads' width
    # -- what the fifth family adds; the defaults build the first four -------
    kda_decay_floor: float = -5.0  # a kda channel's log-decay lies above it
    latent_rank: int = 0  # mla: K and V come up from a normed latent this wide
    rope_head_dim: int = 0  # mla: of head_dim, the part that carries RoPE
    value_head_dim: int = 0  # mla: v's and o's width; head_dim is q's and k's
    shared_experts: int = 0  # experts every token passes, beside the routed
    expert_groups: int = 0  # group-limited selection: the experts' groups
    expert_groups_kept: int = 0  # and how many a token's choice may lie in
    expert_weight_eps: float = 1e-6  # beside the selected scores' sum
    # -- what the sixth family adds; the defaults build the first five -------
    query_rank: int = 0  # mla: q comes up from a normed latent this wide; 0: W_q
    latent_gate: bool = True  # mla: one sigmoid a head gates the read-out
    mtp_weight: float = 0.0  # > 0: ONE multi-token-prediction module, its loss x this

    @property
    def train_report(self) -> tuple:
        """What ``loss`` reports of a TRAINING step beside its loss, by name
        in its ``aux``: the estimator sums these over an epoch's steps inside
        the epoch program and gives the sums to ``epoch_facts``."""
        return ("expert_load", "pairs_dropped", "layers_at_full_bound") + (
            ("mtp_loss",) if self.mtp_built else ())

    def __post_init__(self):
        if self.head_dim is None:
            if self.hidden_size % self.num_heads:
                raise ValueError("query heads must divide the hidden size "
                                 "where no head_dim is given")
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_heads)
        super().__post_init__()

    @classmethod
    def from_config(cls, config: dict, **kw):
        """From a published ``config.json``'s keys, by ``model_type``
        (``granitemoehybrid``, the default, ``lfm2_moe``, ``smallthinker``,
        ``olmo_hybrid``, ``bailing_hybrid`` or ``glm4_moe_lite``). What the
        model does not build is refused, not ignored."""
        family = config.get("model_type", "granitemoehybrid")
        if family == "granitemoehybrid":
            fields = cls._granite_fields(config)
        elif family == "lfm2_moe":
            fields = cls._lfm2_fields(config)
        elif family == "smallthinker":
            fields = cls._smallthinker_fields(config)
        elif family == "olmo_hybrid":
            fields = cls._olmo_hybrid_fields(config)
        elif family == "bailing_hybrid":
            fields = cls._bailing_hybrid_fields(config)
        elif family == "glm4_moe_lite":
            fields = cls._glm4_moe_lite_fields(config)
        else:
            raise ValueError(f"HybridLM builds model_type granitemoehybrid, "
                             f"lfm2_moe, smallthinker, olmo_hybrid, "
                             f"bailing_hybrid and glm4_moe_lite, not "
                             f"{family!r}")
        fields.update(kw)  # attn_impl, dtype, remat, loss_chunk; overrides
        if (fields.get("mtp_weight", 0) > 0
                and not config.get("num_nextn_predict_layers")):
            raise ValueError(
                f"mtp_weight {fields['mtp_weight']} weighs a multi-token-"
                "prediction module the configuration does not have "
                "(num_nextn_predict_layers)")
        fields["dtype"] = jnp.dtype(fields.get("dtype", cls.dtype))
        return cls(**fields)

    @staticmethod
    def _refuse(config: dict, only: dict, why: dict = None) -> None:
        for key, value in only.items():
            if config.get(key, value) != value:
                raise ValueError(
                    f"HybridLM builds {key}={value!r} only, not "
                    f"{config[key]!r}" + (why or {}).get(key, ""))

    @classmethod
    def _granite_fields(cls, config: dict) -> dict:
        """The first ``num_hidden_layers`` entries of ``layer_types``."""
        cls._refuse(config, {
            "num_local_experts": 0, "mamba_n_groups": 1,
            "mamba_proj_bias": False, "attention_bias": False,
            "mamba_conv_bias": True, "tie_word_embeddings": True,
            "position_embedding_type": "nope", "hidden_act": "silu"},
            {"num_local_experts": (
                ": this family's experts are a shared expert beside routed "
                "ones, which from_config builds for bailing_hybrid alone; the "
                "experts FFN kind is lfm2_moe's, smallthinker's and that "
                "family's")})
        if config["mamba_expand"] * config["hidden_size"] != (
                config["mamba_n_heads"] * config["mamba_d_head"]):
            raise ValueError("mamba_expand x hidden_size is not "
                             "mamba_n_heads x mamba_d_head")
        return dict(
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

    @classmethod
    def _lfm2_fields(cls, config: dict) -> dict:
        """``num_hidden_layers`` entries of ``layer_types`` from
        ``share["first_layer"]`` on (a pipeline stage's layers), the first
        ``num_dense_layers`` of them with a dense SwiGLU of
        ``intermediate_size``, the others with routed experts:
        ``num_experts`` of them held here, ``share["first_expert"]`` the
        first, of the ``share["experts_total"]`` the router scores (both
        default to the whole: every expert held)."""
        cls._refuse(config, {
            "conv_bias": False, "norm_topk_prob": True,
            "use_expert_bias": True, "tie_word_embeddings": True})
        share = config.get("share", {})
        first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
        kinds = list(config["layer_types"][first:first + depth])
        names = {"conv": CONV, "full_attention": ATTENTION}
        unknown = sorted(set(kinds) - set(names))
        if unknown or len(kinds) != depth:
            raise ValueError(f"lfm2_moe layers {first}..{first + depth - 1}: "
                             f"layer_types gives {kinds}")
        dense = config["num_dense_layers"]
        head_dim = config["hidden_size"] // config["num_attention_heads"]
        return dict(
            vocab_size=config["vocab_size"],
            layer_types=tuple(names[k] for k in kinds),
            ffn_types=(DENSE,) * dense + (EXPERTS,) * (depth - dense),
            hidden_size=config["hidden_size"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            intermediate_size=config["intermediate_size"],
            conv_kernel=config["conv_L_cache"],
            rope_theta=float(config["rope_theta"]), qk_norm=True,
            expert_width=config["moe_intermediate_size"],
            experts_held=config["num_experts"],
            experts_total=share.get("experts_total", config["num_experts"]),
            first_expert=share.get("first_expert", 0),
            experts_per_token=config["num_experts_per_tok"],
            routed_scaling=float(config["routed_scaling_factor"]),
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=head_dim ** -0.5, logits_scaling=1.0,
            rms_eps=float(config["norm_eps"]))

    @classmethod
    def _smallthinker_fields(cls, config: dict) -> dict:
        """``num_hidden_layers`` layers from ``share["first_layer"]`` on (a
        pipeline stage's), each grouped-query attention under routed ReGLU
        experts: layer l sees ``sliding_window_size`` keys where
        ``sliding_window_layout[l]`` is 1 and all of them where 0, and
        carries RoPE where ``rope_layout[l]`` is 1 (two independent keys:
        each layer reads its own entry of each). ``moe_num_primary_experts``
        experts are held here, ``share["first_expert"]`` the first, of the
        ``share["experts_total"]`` the router scores (both default to the
        whole). The layers built must be a whole number of the layouts'
        periods from a period's first layer."""
        cls._refuse(config, {
            "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
            "tie_word_embeddings": False, "rope_scaling": None},
            {"moe_primary_router_apply_softmax": (
                ": the sigmoid rule of this family (scores normalised over "
                "the selected, no bias) is not built")})
        share = config.get("share", {})
        first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
        layout = list(zip(config["sliding_window_layout"],
                          config["rope_layout"]))
        period = next(p for p in range(1, len(layout) + 1)
                      if not len(layout) % p
                      and all(layout[i] == layout[i % p]
                              for i in range(len(layout))))
        if (first % period or depth % period or depth <= 0
                or first + depth > len(layout)):
            raise ValueError(
                f"smallthinker layers {first}..{first + depth - 1} of "
                f"{len(layout)}: not a whole number of periods of {period} "
                "layers (sliding_window_layout, rope_layout) from a "
                "period's first layer")
        held = config["moe_num_primary_experts"]
        return dict(
            vocab_size=config["vocab_size"],
            layer_types=(ATTENTION,) * depth, ffn_types=(EXPERTS,) * depth,
            hidden_size=config["hidden_size"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            attention_windows=tuple(
                config["sliding_window_size"] if windowed else 0
                for windowed, _ in layout[first:first + depth]),
            rope_layers=tuple(
                int(bool(rope)) for _, rope in layout[first:first + depth]),
            rope_theta=float(config["rope_theta"]),
            expert_width=config["moe_ffn_hidden_size"],
            experts_held=held,
            experts_total=share.get("experts_total", held),
            first_expert=share.get("first_expert", 0),
            experts_per_token=config["moe_num_active_primary_experts"],
            expert_scoring="softmax", expert_activation="relu",
            router_input="block", tied_head=False,
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=config["head_dim"] ** -0.5,
            logits_scaling=1.0, rms_eps=float(config["rms_norm_eps"]))

    @classmethod
    def _olmo_hybrid_fields(cls, config: dict) -> dict:
        """``num_hidden_layers`` entries of ``layer_types`` from
        ``share["first_layer"]`` on (a pipeline stage's layers):
        ``linear_attention`` is the gated delta-rule mixer,
        ``full_attention`` softmax attention without positions
        (``rope_parameters.rope_theta`` null) under the family's q/k norms,
        whose statistic runs over the whole projection HELD here; a dense
        SwiGLU in every layer; a sub-layer's OUTPUT is normed before the
        residual addition. ``num_attention_heads``, ``num_key_value_heads``
        and ``linear_num_*_heads`` are the heads held here, of
        ``share["heads_total"]`` (default: all), so ``head_dim`` is a key of
        its own where a share is held (default: hidden over the heads)."""
        cls._refuse(config, {
            "attention_bias": False, "tie_word_embeddings": False,
            "hidden_act": "silu", "linear_allow_neg_eigval": True},
            {"linear_allow_neg_eigval": (
                ": beta = 2 sigmoid(.), a step that may reflect, is the one "
                "built")})
        if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
            raise ValueError(
                "HybridLM builds as many delta-rule key heads as value "
                f"heads, not {config['linear_num_key_heads']} and "
                f"{config['linear_num_value_heads']}")
        share = config.get("share", {})
        first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
        kinds = list(config["layer_types"][first:first + depth])
        names = {"linear_attention": DELTA, "full_attention": ATTENTION}
        if set(kinds) - set(names) or len(kinds) != depth:
            raise ValueError(f"olmo_hybrid layers {first}..{first + depth - 1}"
                             f": layer_types gives {kinds}")
        heads = config["num_attention_heads"]
        head_dim = config.get("head_dim") or config["hidden_size"] // heads
        theta = (config.get("rope_parameters") or {}).get("rope_theta")
        return dict(
            vocab_size=config["vocab_size"],
            layer_types=tuple(names[k] for k in kinds),
            hidden_size=config["hidden_size"],
            num_heads=heads, num_kv_heads=config["num_key_value_heads"],
            head_dim=head_dim,
            intermediate_size=config["intermediate_size"],
            delta_heads=config["linear_num_key_heads"],
            delta_heads_total=share.get(
                "heads_total", config["linear_num_key_heads"]),
            delta_key_dim=config["linear_key_head_dim"],
            delta_value_dim=config["linear_value_head_dim"],
            delta_conv=config["linear_conv_kernel_dim"],
            norm_placement="post", qk_norm=True, qk_norm_over="projection",
            rope_theta=float(theta or 0.0), tied_head=False,
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=head_dim ** -0.5, logits_scaling=1.0,
            rms_eps=float(config["rms_norm_eps"]))

    @classmethod
    def _bailing_hybrid_fields(cls, config: dict) -> dict:
        """``num_hidden_layers`` layers from ``share["first_layer"]`` on (a
        pipeline stage's): published layer l (from 0) is ``mla`` where
        ``(l + 1) % layer_group_size`` is 0 and ``kda`` elsewhere; the first
        ``first_k_dense_replace`` of the layers built carry a dense SwiGLU of
        ``intermediate_size``, the others ``num_experts`` held experts,
        ``share["first_expert"]`` the first, of the
        ``share["experts_total"]`` the router scores (both default to the
        whole), beside ``num_shared_experts`` shared ones. The selection is
        group-limited (``n_group``, ``topk_group``). Refused: a
        multi-token-prediction layer whose loss counts, a SwiGLU clamp in a
        layer built, nGPT, a value norm, low-rank KDA gates, a shared expert
        of another width than the routed ones."""
        cls._refuse(config, {
            "moe_shared_expert_intermediate_size":
                config["moe_intermediate_size"],
            "use_nGPT": False, "value_norm": False, "up_proj_norm": False,
            "use_bias": False, "use_qkv_bias": False,
            "tie_word_embeddings": False, "q_lora_rank": None,
            "rope_scaling": None, "score_function": "sigmoid",
            "topk_method": "noaux_tc", "norm_topk_prob": True,
            "moe_router_enable_expert_bias": True, "hidden_act": "silu",
            "kda_safe_gate": True, "no_kda_lora": True, "use_kda_lora": False,
            "linear_silu": True, "use_qk_norm": True, "group_norm_size": 1,
            "gated_attention_proj_granularity_type": "head_wise",
            "rope_interleave": True, "scale_router_input": False,
            "num_kv_heads_for_linear_attn": 0, "use_mla_nope": False})
        if config.get("num_nextn_predict_layers") and config.get(
                "mtp_loss_scaling_factor"):
            raise ValueError(
                "HybridLM builds no multi-token-prediction layer: "
                f"mtp_loss_scaling_factor {config['mtp_loss_scaling_factor']!r}"
                " gives its loss a weight (at 0 it takes no gradient and is "
                "left out)")
        share = config.get("share", {})
        first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            if any(config.get(key, ())[first:first + depth]):
                raise ValueError(
                    f"HybridLM builds no SwiGLU clamp: {key} is not 0 in "
                    f"layers {first}..{first + depth - 1}")
        period = config["layer_group_size"]
        dense = config["first_k_dense_replace"]
        nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
        held = config["num_experts"]
        return dict(
            vocab_size=config["vocab_size"],
            layer_types=tuple(MLA if (layer + 1) % period == 0 else KDA
                              for layer in range(first, first + depth)),
            ffn_types=(DENSE,) * dense + (EXPERTS,) * (depth - dense),
            hidden_size=config["hidden_size"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=nope + rope, rope_head_dim=rope,
            value_head_dim=config["v_head_dim"],
            latent_rank=config["kv_lora_rank"],
            rope_theta=float(config["rope_theta"]),
            intermediate_size=config["intermediate_size"],
            delta_heads=config["num_attention_heads"],
            delta_heads_total=config["num_attention_heads"],
            delta_key_dim=config["head_dim"],
            delta_value_dim=config["head_dim"],
            delta_conv=config["short_conv_kernel_size"],
            kda_decay_floor=float(config["kda_lower_bound"]),
            expert_width=config["moe_intermediate_size"],
            experts_held=held,
            experts_total=share.get("experts_total", held),
            first_expert=share.get("first_expert", 0),
            experts_per_token=config["num_experts_per_tok"],
            routed_scaling=float(config["routed_scaling_factor"]),
            shared_experts=config["num_shared_experts"],
            expert_groups=config["n_group"],
            expert_groups_kept=config["topk_group"],
            expert_weight_eps=1e-20, tied_head=False,
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=(nope + rope) ** -0.5, logits_scaling=1.0,
            rms_eps=float(config["rms_norm_eps"]))

    @classmethod
    def _glm4_moe_lite_fields(cls, config: dict) -> dict:
        """``num_hidden_layers`` layers from ``share["first_layer"]`` on (a
        pipeline stage's), every one ``mla`` with a query that comes up from a
        normed latent of ``q_lora_rank`` and no output gate; published layer
        l (from 0) carries a dense SwiGLU of ``intermediate_size`` where l <
        ``first_k_dense_replace`` and elsewhere ``n_routed_experts`` held
        experts, ``share["first_expert"]`` the first, of the
        ``share["experts_total"]`` the router scores (both default to the
        whole), beside ``n_shared_experts`` shared ones of the same width;
        ``n_group`` 1 is no group limit. ``num_nextn_predict_layers`` 1: one
        multi-token-prediction module, BUILT WHERE THE CALLER WEIGHS ITS LOSS
        (``mtp_weight``: ``config.json`` has no key for lambda, so a
        configuration as run states it; without one nothing of the module is
        built, and a weight without a module is refused by ``from_config``).
        Refused: positions scaled, a bias, another selection rule than
        ``noaux_tc``, unnormalised weights, a tied head, more than one
        module, a partial rotary factor, fewer key/value heads than query
        heads."""
        cls._refuse(config, {
            "attention_bias": False, "hidden_act": "silu",
            "rope_scaling": None, "topk_method": "noaux_tc",
            "norm_topk_prob": True, "tie_word_embeddings": False,
            "partial_rotary_factor": 1,
            "num_key_value_heads": config["num_attention_heads"]},
            {"partial_rotary_factor": (
                ": RoPE turns all of qk_rope_head_dim, and nothing else")})
        modules = config.get("num_nextn_predict_layers", 0)
        if modules > 1:
            raise ValueError(
                "HybridLM builds one multi-token-prediction module, not "
                f"num_nextn_predict_layers={modules!r}")
        if not config.get("q_lora_rank"):
            raise ValueError(
                "HybridLM builds glm4_moe_lite's query through a latent: "
                f"q_lora_rank={config.get('q_lora_rank')!r}")
        share = config.get("share", {})
        first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
        dense = config["first_k_dense_replace"]
        nope, rope = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
        held = config["n_routed_experts"]
        groups = config.get("n_group", 1)
        return dict(
            vocab_size=config["vocab_size"],
            layer_types=(MLA,) * depth,
            ffn_types=tuple(DENSE if layer < dense else EXPERTS
                            for layer in range(first, first + depth)),
            hidden_size=config["hidden_size"],
            num_heads=config["num_attention_heads"],
            num_kv_heads=config["num_key_value_heads"],
            head_dim=nope + rope, rope_head_dim=rope,
            value_head_dim=config["v_head_dim"],
            latent_rank=config["kv_lora_rank"],
            query_rank=config["q_lora_rank"], latent_gate=False,
            rope_theta=float(config["rope_theta"]),
            intermediate_size=config["intermediate_size"],
            expert_width=config["moe_intermediate_size"],
            experts_held=held,
            experts_total=share.get("experts_total", held),
            first_expert=share.get("first_expert", 0),
            experts_per_token=config["num_experts_per_tok"],
            routed_scaling=float(config["routed_scaling_factor"]),
            shared_experts=config["n_shared_experts"],
            expert_groups=groups if groups > 1 else 0,
            expert_groups_kept=config.get("topk_group", 1) if groups > 1 else 0,
            expert_weight_eps=1e-20, tied_head=False,
            embedding_multiplier=1.0, residual_multiplier=1.0,
            attention_multiplier=(nope + rope) ** -0.5, logits_scaling=1.0,
            rms_eps=float(config["rms_norm_eps"]))

    # -- shapes ----------------------------------------------------------------
    @property
    def mtp_built(self) -> bool:
        """Whether the multi-token-prediction module is there: a module
        whose loss weighs nothing takes no gradient and is left out."""
        return self.mtp_weight > 0

    @property
    def mtp_kinds(self) -> tuple:
        """The module's block, (mixer kind, FFN kind): the last layer's."""
        return (self.layer_types[-1], self.ffn_kinds[-1])

    @property
    def blocks(self) -> tuple:
        """(mixer kind, FFN kind, window) of every block a step runs: the
        layers, then the multi-token-prediction module's where it is built."""
        main = tuple(zip(self.layer_types, self.ffn_kinds, self.layer_windows))
        return main + ((self.mtp_kinds + (0,),) if self.mtp_built else ())

    @property
    def value_width(self) -> int:
        """An attention head's v and o: ``head_dim`` but in an ``mla``
        layer."""
        return self.value_head_dim or self.head_dim

    @property
    def attention_width(self) -> int:
        """Query heads x head_dim: what ``wq`` gives and ``wo`` takes."""
        return self.num_heads * self.head_dim

    @property
    def layer_windows(self) -> tuple:
        """Keys a layer's queries see, a number a layer; 0: all of them."""
        return tuple(self.attention_windows) or (0,) * len(self.layer_types)

    @property
    def layer_ropes(self) -> tuple:
        """Whether a layer's attention carries RoPE, a flag a layer."""
        return tuple(bool(r) for r in self.rope_layers) or (
            bool(self.rope_theta),) * len(self.layer_types)

    def attention_pairs(self, t: int) -> int:
        """(query, key) pairs the attention layers of a row of ``t`` tokens
        need: t (t + 1) / 2 for a global layer, W (W + 1) / 2 + (t - W) W
        for a layer that sees W < t keys."""
        def pairs(window):
            w = min(window, t) if window else t
            return w * (w + 1) // 2 + (t - w) * w

        return sum(pairs(window) for kind, _, window in self.blocks
                   if kind in (ATTENTION, MLA))

    @property
    def mamba_inner(self) -> int:
        return self.mamba_heads * self.mamba_head_dim

    @property
    def ffn_kinds(self) -> tuple:
        return tuple(self.ffn_types) or (DENSE,) * len(self.layer_types)

    @property
    def expert_layers(self) -> int:
        """Expert layers a step runs, the module's among them."""
        return sum(ffn == EXPERTS for _, ffn, _ in self.blocks)

    def matrix_shapes(self, kind: str, ffn: str = DENSE) -> dict:
        """{name: (in, out)} of one layer's matrices, a held expert's as one
        of its own: what a token passes through."""
        d, f, inner = self.hidden_size, self.intermediate_size, self.mamba_inner
        if ffn == DENSE:
            after = {"w_in": (d, 2 * f), "w_out": (f, d)}
        else:
            after = {"router": (d, self.experts_total),
                     "w13": (d, 2 * self.expert_width),
                     "w2": (self.expert_width, d)}
            if self.shared_experts:  # side by side, as one SwiGLU
                wide = self.shared_experts * self.expert_width
                after.update(shared_in=(d, 2 * wide), shared_out=(wide, d))
        if kind == ATTENTION:
            kv, wide = self.num_kv_heads * self.head_dim, self.attention_width
            return {"wq": (d, wide), "wk": (d, kv), "wv": (d, kv),
                    "wo": (wide, d), **after}
        if kind == CONV:
            return {"in_proj": (d, 3 * d), "out_proj": (d, d), **after}
        if kind == DELTA:
            heads = self.delta_heads
            keys, values = heads * self.delta_key_dim, heads * self.delta_value_dim
            return {"wq": (d, keys), "wk": (d, keys), "wv": (d, values),
                    "wg": (d, values), "wa": (d, heads), "wb": (d, heads),
                    "wo": (values, d), **after}
        if kind == KDA:
            heads = self.delta_heads
            keys, values = heads * self.delta_key_dim, heads * self.delta_value_dim
            return {"wq": (d, keys), "wk": (d, keys), "wv": (d, values),
                    "wf": (d, keys), "wb": (d, heads), "wg": (d, heads),
                    "wo": (values, d), **after}
        if kind == MLA:
            heads, nope = self.num_heads, self.head_dim - self.rope_head_dim
            query = {"wq": (d, self.attention_width)} if not self.query_rank else {
                "wqa": (d, self.query_rank),
                "wqb": (self.query_rank, self.attention_width)}
            gate = {"wg": (d, heads)} if self.latent_gate else {}
            return {**query,
                    "wkva": (d, self.latent_rank + self.rope_head_dim),
                    "wkvb": (self.latent_rank,
                             heads * (nope + self.value_width)),
                    **gate, "wo": (heads * self.value_width, d),
                    **after}
        return {"in_proj": (d, 2 * inner + 2 * self.mamba_state
                            + self.mamba_heads),
                "out_proj": (inner, d), **after}

    def expert_row_bound(self, tokens: int) -> int:
        """Rows of an expert layer's buffer for a batch of ``tokens`` IN THE
        WORST CASE, every choice held: what the facts and the benchmark's
        readers count with, and the bound of the layer's overflow path. It
        is no longer what the layer allocates: that is
        ``expert_likely_row_bound`` wherever the batch's
        load fits it (``ops.experts.routed_experts`` chooses by the load)."""
        return experts_op.row_bound_for(tokens * self.experts_per_token)

    def expert_likely_row_bound(self, tokens: int) -> int:
        """Rows the layer runs at wherever the load fits them
        (``ops.experts.likely_row_bound``: ``SLACK`` x the even share of
        the held experts, its margin wider under a quarter share); the worst
        case where every expert is held."""
        return experts_op.likely_row_bound(
            tokens * self.experts_per_token, self.experts_held,
            self.experts_total)

    def setup(self):
        d = self.hidden_size
        kinds, ffns = self.layer_types, self.ffn_kinds
        for kind in kinds:
            if kind not in (MAMBA, ATTENTION, CONV, DELTA, KDA, MLA):
                raise ValueError(f"layer kind {kind!r} is none of {MAMBA!r}, "
                                 f"{ATTENTION!r}, {CONV!r}, {DELTA!r}, "
                                 f"{KDA!r}, {MLA!r}")
        if MLA in kinds and not (
                0 < self.rope_head_dim < self.head_dim
                and self.rope_head_dim % 2 == 0 and self.latent_rank > 0
                and self.rope_theta > 0):
            raise ValueError(
                f"a latent of {self.latent_rank}, RoPE (theta "
                f"{self.rope_theta}) on {self.rope_head_dim} of a key's "
                f"{self.head_dim}: not a latent-attention layer")
        if self.mtp_weight < 0 or self.query_rank < 0:
            raise ValueError(
                f"mtp_weight {self.mtp_weight}, query_rank "
                f"{self.query_rank}: no negative weight or rank")
        if self.mtp_built and self.expert_placement:
            raise ValueError("expert_placement places the layers' experts; "
                             "the multi-token-prediction module's are not")
        if (DELTA in kinds or KDA in kinds) and not (
                0 < self.delta_heads <= (self.delta_heads_total
                                         or self.delta_heads)
                and self.delta_key_dim > 0 and self.delta_value_dim > 0):
            raise ValueError(
                f"{self.delta_heads} delta-rule heads of "
                f"{self.delta_heads_total or self.delta_heads}, keys of "
                f"{self.delta_key_dim}, values of {self.delta_value_dim}: "
                "not a delta-rule layer's share")
        if KDA in kinds and not (
                delta_rule.LOG_DECAY_FLOOR <= self.kda_decay_floor <= 0):
            raise ValueError(
                f"kda_decay_floor {self.kda_decay_floor}: the channel-decay "
                "scan bears log-decays a token and channel from "
                f"{delta_rule.LOG_DECAY_FLOOR} to 0 (a sub-block's operands "
                "are decayed from its middle row: float32's exponent)")
        if (self.norm_placement not in ("pre", "post")
                or self.qk_norm_over not in ("head", "projection")):
            raise ValueError(
                f"norm_placement {self.norm_placement!r}, qk_norm_over "
                f"{self.qk_norm_over!r}: not among ('pre', 'post'), "
                "('head', 'projection')")
        if len(ffns) != len(kinds) or set(ffns) - {DENSE, EXPERTS}:
            raise ValueError(f"ffn_types {ffns} does not give {DENSE!r} or "
                             f"{EXPERTS!r} for each of {len(kinds)} layers")
        if self.num_heads % self.num_kv_heads:
            raise ValueError("K/V heads must divide the query heads")
        if (len(self.layer_windows) != len(kinds)
                or len(self.layer_ropes) != len(kinds)):
            raise ValueError(
                f"attention_windows {tuple(self.attention_windows)} and "
                f"rope_layers {tuple(self.rope_layers)} give a number for "
                f"each of {len(kinds)} layers, or are empty")
        if (self.expert_scoring not in experts_op.SCORINGS
                or self.expert_activation not in experts_op.ACTIVATIONS
                or self.router_input not in ("ffn", "block")):
            raise ValueError(
                f"expert_scoring {self.expert_scoring!r}, expert_activation "
                f"{self.expert_activation!r}, router_input "
                f"{self.router_input!r}: not among {experts_op.SCORINGS}, "
                f"{tuple(experts_op.ACTIVATIONS)}, ('ffn', 'block')")
        if EXPERTS in ffns and not (
                0 < self.experts_per_token <= self.experts_total
                and 0 < self.experts_held
                and self.first_expert + self.experts_held <= self.experts_total
                and self.first_expert >= 0 and self.expert_width > 0):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} of "
                f"{self.experts_total}, {self.experts_per_token} a token, "
                f"width {self.expert_width}: not an expert layer's share")
        if self.expert_placement and [sorted(o) for o in
                                      self.expert_placement] != [
                list(range(self.experts_total))] * ffns.count(EXPERTS):
            raise ValueError(
                "expert_placement gives each expert layer a permutation of "
                f"its {self.experts_total} experts, or is empty")
        matrix = nn.initializers.normal(0.02)

        def layer(kind, ffn, order=None):
            def init(rng):
                shapes = self.matrix_shapes(kind, ffn)
                keys = jax.random.split(rng, len(shapes) + 3)
                out = {}
                for (name, shape), k in zip(shapes.items(), keys):
                    if name in ("w13", "w2"):  # one a held expert, stacked
                        shape = (self.experts_held,) + shape
                    out[name] = matrix(k, shape, jnp.float32)
                if order is not None:  # slot j scores the seeded expert order[j]
                    out["router"] = out["router"][:, jnp.asarray(order)]
                out.update(norm1=jnp.ones((d,), jnp.float32),
                           norm2=jnp.ones((d,), jnp.float32))
                if kind == MAMBA:
                    out.update(self._mamba_vectors(keys[-3:]))
                elif kind == CONV:
                    # as torch initialises a depthwise Conv1d
                    bound = self.conv_kernel ** -0.5
                    out["conv_w"] = jax.random.uniform(
                        keys[-1], (self.conv_kernel, d), jnp.float32,
                        -bound, bound)
                elif kind == DELTA:
                    out.update(self._delta_vectors(keys[-3:]))
                elif kind == KDA:
                    out.update(self._delta_vectors(
                        keys[-3:], self.delta_heads * self.delta_key_dim))
                elif kind == MLA:
                    out["kv_norm"] = jnp.ones((self.latent_rank,),
                                              jnp.float32)
                    if self.query_rank:
                        out["q_norm"] = jnp.ones((self.query_rank,),
                                                 jnp.float32)
                elif self.qk_norm:
                    whole = self.qk_norm_over == "projection"
                    out.update(
                        q_norm=jnp.ones((self.head_dim * (
                            self.num_heads if whole else 1),), jnp.float32),
                        k_norm=jnp.ones((self.head_dim * (
                            self.num_kv_heads if whole else 1),), jnp.float32))
                if ffn == EXPERTS and self.expert_scoring == "sigmoid":
                    spread = self.expert_bias_spread
                    out["expert_bias"] = jax.random.uniform(
                        keys[-2], (self.experts_total,), jnp.float32,
                        -spread, spread) if spread else jnp.zeros(
                            (self.experts_total,), jnp.float32)
                return out
            return init

        self.embed = self.param(
            "embed", nn.initializers.normal(self.embed_std),
            (self.vocab_size, d), jnp.float32)
        placed = iter(self.expert_placement or [None] * len(kinds))
        self.layers = [
            self.param(f"layer_{i}", layer(
                kind, ffn, next(placed) if ffn == EXPERTS else None))
            for i, (kind, ffn) in enumerate(zip(kinds, ffns))]
        self.final_norm = self.param("final_norm", nn.initializers.ones, (d,),
                                     jnp.float32)
        if not self.tied_head:
            self.head_w = self.param("head", matrix, (d, self.vocab_size),
                                     jnp.float32)
        if self.mtp_built:
            def module(rng):
                block, combine = jax.random.split(rng)
                return {**layer(*self.mtp_kinds)(block),
                        "eh_proj": matrix(combine, (2 * d, d), jnp.float32),
                        **{name: jnp.ones((d,), jnp.float32)
                           for name in ("enorm", "hnorm", "final_norm")}}

            self.mtp = self.param("mtp_0", module)

    @staticmethod
    def _decay_vectors(keys, heads: int, taps: int, channels: int,
                       biases: int = 0) -> dict:
        """What a mixer with a decay a head and a depthwise convolution
        seeds, by Mamba-2's published initialisation: dt log-uniform in
        [0.001, 0.1] through the inverse softplus into ``dt_bias`` (one a
        head, or ``biases`` of them: a decay a channel), ``A_log`` the log
        of U(1, 16); the convolution as torch initialises a depthwise
        ``Conv1d`` (uniform in +-1/sqrt(k))."""
        dt = jnp.exp(jax.random.uniform(
            keys[0], (biases or heads,), jnp.float32, math.log(1e-3),
            math.log(1e-1)))
        bound = taps ** -0.5
        return {
            "conv_w": jax.random.uniform(keys[2], (taps, channels),
                                         jnp.float32, -bound, bound),
            "dt_bias": _inverse_softplus(jnp.maximum(dt, 1e-4)),
            "A_log": jnp.log(jax.random.uniform(
                keys[1], (heads,), jnp.float32, 1.0, 16.0)),
        }

    def _mamba_vectors(self, keys) -> dict:
        """``_decay_vectors`` with a zero convolution bias, ``D`` ones and
        the gated norm's gain."""
        channels = self.mamba_inner + 2 * self.mamba_state
        return {
            **self._decay_vectors(keys, self.mamba_heads, self.mamba_conv,
                                  channels),
            "conv_b": jnp.zeros((channels,), jnp.float32),
            "D": jnp.ones((self.mamba_heads,), jnp.float32),
            "gate_norm": jnp.ones((self.mamba_inner,), jnp.float32),
        }

    def _delta_vectors(self, keys, biases: int = 0) -> dict:
        """``_decay_vectors`` over the q | k | v channels (no bias) and the
        read-out norm's gain over a value head."""
        heads = self.delta_heads
        return {
            **self._decay_vectors(
                keys, heads, self.delta_conv,
                heads * (2 * self.delta_key_dim + self.delta_value_dim),
                biases),
            "gate_norm": jnp.ones((self.delta_value_dim,), jnp.float32),
        }

    # -- what the estimator writes down once per fit (fit_facts rule) --------
    def fit_facts(self, x) -> dict:
        """``x`` is a sample of the staged feature, [rows, T + 1] ids: a row
        holds T predicted tokens. ``flops_per_row`` is the model's FLOPs of
        a training step on one row, forward + backward = 3 x forward, from
        shapes (``flops_per_row_parts``); recomputation does not count, and
        the experts are counted AT THE UNIFORM SHARE (tokens x
        experts_per_token x held / total pairs a layer): a number from
        shapes, so ``estimator.mfu`` does not move with the routing.
        ``experts.token_rows_gathered_per_pass``: the rows one token-side
        sum of an expert layer gathers for a batch row at the likely bound
        (``ops.experts.token_rows_gathered``: bound + tokens token-ordered,
        tokens x k per choice), for a trace's reader to hold the gathers it
        sees against. ``attention_backward`` and its two numbers: the form
        the attention layers' backward pass takes by layer kind, and
        ``attention_grid`` with its share: the grid a kind's calls step over
        (``transformer.attention_backward_facts``)."""
        t = x.shape[1] - 1
        parts = self.flops_per_row_parts(t)
        kept = self._remat_keeps(t)
        facts = {
            "layer_kinds": ",".join(self.layer_types),
            "layer_kinds.mamba": self.layer_types.count(MAMBA),
            "layer_kinds.attention": self.layer_types.count(ATTENTION),
            "layer_kinds.conv": self.layer_types.count(CONV),
            "ssd_chunk": min(self.mamba_chunk, t),
            "ssd_flops_per_row": parts["scan"],
            "remat": bool(self.remat), "remat_keeps": ",".join(kept),
            "remat_kept_bytes_per_row": sum(kept.values()),
            **LOSS_FACTS,
            "tokens_per_row": t, "flops_per_row": sum(parts.values())}
        if self.expert_layers:
            facts.update({
                "ffn_kinds": ",".join(self.ffn_kinds),
                "experts.held": self.experts_held,
                "experts.total": self.experts_total,
                "experts.per_token": self.experts_per_token,
                "experts.layers": self.expert_layers,
                "experts.rows_per_row": self.expert_row_bound(t),
                "experts.rows_likely_per_row": self.expert_likely_row_bound(t),
                "experts.token_rows_gathered_per_pass":
                    experts_op.token_rows_gathered(
                        self.expert_likely_row_bound(t), t,
                        self.experts_per_token),
                "experts.flops_per_row": parts["experts"],
                "experts.flops_counted": "uniform share"})
            if self.shared_experts:
                facts["experts.shared"] = self.shared_experts
            if self.expert_groups:
                facts.update({"experts.groups": self.expert_groups,
                              "experts.groups_kept": self.expert_groups_kept})
        delta = self.layer_types.count(DELTA) + self.layer_types.count(KDA)
        if delta:
            by_kind = {kind: self.layer_types.count(kind)
                       for kind in (DELTA, KDA) if kind in self.layer_types}
            decays = [{DELTA: "head", KDA: "channel"}[kind] for kind in by_kind]
            facts.update({
                **{f"layer_kinds.{kind}": n for kind, n in by_kind.items()},
                "delta.decay": ",".join(decays),
                "delta.scan": ",".join(delta_rule.SCAN[d] for d in decays),
                "delta.heads_held": self.delta_heads,
                "delta.heads_total": self.delta_heads_total or self.delta_heads,
                "delta.chunk": min(delta_rule.CHUNK, t),
                "delta.flops_per_row": parts["delta"],
                # float32 [Dv, Dk] a held head and layer: what a row carries
                # from token to token
                "delta.state_bytes_per_row": delta * 4 * self.delta_heads
                * self.delta_key_dim * self.delta_value_dim})
            # what runs the chain AROUND the scan: a ``kda`` mixer's is
            # ``ops.kda_mixer``'s fused kernels wherever they take the sizes;
            # a ``delta`` mixer's scan is plain ``jnp`` and has no layout of
            # its own to share
            mixers = {kind: self._kda_mixer(t) if kind == KDA else (
                "xla", "a decay a head: no kernel runs its scan")
                for kind in by_kind}
            whys = [why_not for _, why_not in mixers.values() if why_not]
            facts["delta.mixer"] = ",".join(m for m, _ in mixers.values())
            if whys:
                facts["delta.mixer_why_not"] = "; ".join(whys)
            if mixers.get(KDA, ("",))[0] == "kernel":
                facts["delta.mixer_fused_layers"] = by_kind[KDA]
        attention = [w for kind, w in zip(self.layer_types, self.layer_windows)
                     if kind == ATTENTION]
        kinds = {"global": attention.count(0),
                 "window": sum(1 for w in attention if w),
                 "latent": sum(kind == MLA for kind, _, _ in self.blocks)}
        facts.update(attention_backward_facts(
            self.attn_impl, t, self.head_dim, self.dtype,
            {kind: n for kind, n in kinds.items() if n}, self.value_width,
            max(attention, default=0) or None))
        if kinds["latent"]:
            facts.update({
                "layer_kinds.mla": self.layer_types.count(MLA),
                "attention.latent_rank": self.latent_rank,
                "attention.key_width": self.head_dim,
                "attention.value_width": self.value_width})
            if self.query_rank:
                facts["attention.query_rank"] = self.query_rank
        if self.mtp_built:
            # a block more than ``layer_kinds`` lists, and a second pass of
            # the head: both are in ``flops_per_row``, the expert layers'
            # counts and ``attention.backward_fused_layers``
            facts.update({"mtp.weight": self.mtp_weight,
                          "mtp.block": ",".join(self.mtp_kinds)})
        if any(self.layer_windows):
            facts.update({
                "layer_kinds.window": kinds["window"],
                "layer_kinds.global": kinds["global"],
                "attention.window": max(attention),
                # what the step's attention NEEDS: the pairs the windows keep
                "attention.pairs_per_row": self.attention_pairs(t)})
        return facts

    def flops_per_row_parts(self, t: int) -> dict:
        """Model FLOPs of a training step on a row of ``t`` tokens by part:
        ``layers`` (6 x matrix parameters x tokens, a convolution's 2 k a
        channel beside them; routers here, experts not), ``scan`` (the dual
        form's four products at this chunk size, causal pairs inside a
        chunk), ``attention`` (causal: t (t + 1) / 2 kept pairs, a window
        layer's fewer: ``attention_pairs``), ``head``
        (the embedding or the head, once), with delta-rule layers ``delta``
        (the recurrence's own 6 Dk Dv a token and held head:
        ``ops.delta_rule.recurrence_flops``) and, with expert layers,
        ``experts`` (6 x one expert's parameters x the uniform share of the
        pairs). A multi-token-prediction module's block counts as a layer
        (``blocks``), its ``eh_proj`` among the matrices, and the head
        twice (row T - 1 of the second pass weighs 0 and is run all the
        same: counted)."""
        d, n = self.hidden_size, self.mamba_state
        heads, p = self.mamba_heads, self.mamba_head_dim
        mamba = self.layer_types.count(MAMBA)
        matrices = sum(
            a * b for kind, ffn, _ in self.blocks
            for name, (a, b) in self.matrix_shapes(kind, ffn).items()
            if name not in ("w13", "w2"))
        heads_run = 1
        if self.mtp_built:  # ``eh_proj``, and the shared head a second time
            matrices += 2 * d * d
            heads_run = 2
        delta = self.layer_types.count(DELTA) + self.layer_types.count(KDA)
        conv = (mamba * self.mamba_conv * (self.mamba_inner + 2 * n)
                + self.layer_types.count(CONV) * self.conv_kernel * d
                + delta * self.delta_conv * self.delta_heads
                * (2 * self.delta_key_dim + self.delta_value_dim))
        q = min(self.mamba_chunk, t)
        pairs = (t // q) * (q * (q + 1) // 2)  # kept (i, j) pairs of a row
        scan = mamba * (2 * n * pairs + 2 * heads * p * pairs
                        + 2 * 2 * heads * p * n * t)
        parts = {
            "layers": 6 * (matrices + conv) * t,
            "scan": 3 * scan,
            # q.k over head_dim and p.v over the values' width, a kept pair
            "attention": 6 * self.num_heads * (
                self.head_dim + self.value_width) * self.attention_pairs(t),
            "head": heads_run * 6 * d * self.vocab_size * t}
        if delta:
            # what the RECURRENCE needs, whatever implements it
            parts["delta"] = 3 * delta * delta_rule.recurrence_flops(
                t, self.delta_heads, self.delta_key_dim, self.delta_value_dim)
        if self.expert_layers:
            # tokens x k x held / total pairs a layer, whole numbers here
            pairs_here = (t * self.experts_per_token * self.experts_held
                          // self.experts_total)
            parts["experts"] = (self.expert_layers * 6 * 3 * d
                                * self.expert_width * pairs_here)
        return parts

    def _remat_keeps(self, t: int) -> dict:
        """{name: bytes the layers keep of a row of ``t`` tokens for the
        backward pass}, of ``REMAT_KEEPS`` (and ``EXPERT_KEEPS`` with expert
        layers): nothing without ``remat`` (then everything is kept), the
        attention's two only where the flash kernel names them."""
        if not self.remat:
            return {}
        itemsize = jnp.dtype(self.dtype).itemsize
        wide = t * self.hidden_size * itemsize
        attention = sum(kind in (ATTENTION, MLA) for kind, _, _ in self.blocks)
        sizes = {"attn_out": attention * t * self.num_heads
                 * self.value_width * itemsize,
                 "attn_lse": attention * 4 * self.num_heads * t,
                 "mlp_out": len(self.blocks) * wide}
        flash = self.attn_impl in ("flash", "ulysses_flash")
        kept = {name: sizes[name] for name in REMAT_KEEPS
                if flash or name not in SAVED_RESIDUALS}
        if self.expert_layers:
            # int32: of a pair its choice, its place by expert and the row
            # that is (the sort and its inverse), and the held pairs in token
            # order by row and by pair; of a token its first place there
            kept[experts_op.KEPT] = self.expert_layers * 4 * (
                5 * t * self.experts_per_token + t)
        if KDA in self.layer_types:
            rows = self.layer_types.count(KDA) * t * self.delta_heads
            keys, values = rows * self.delta_key_dim, rows * self.delta_value_dim
            kept[delta_rule.SAVED_OUTPUT] = values * itemsize
            if self._kda_mixer(t)[0] == "kernel":
                # what the fused chain hands the scan (q, k, v in the compute
                # dtype, the log-decay float32: the operands its backward
                # call reads) and W_o's input: kept, no call of the chain
                # runs twice a step
                kept[kda_mixer.OPERANDS] = (
                    (2 * keys + values) * itemsize + 4 * keys)
                kept[kda_mixer.READ_OUT] = values * itemsize
        return kept

    def epoch_facts(self, report: dict, steps: int) -> dict:
        """What an epoch's summed ``train_report`` says, for the estimator's
        counters and gauges (``model.<name>``): ``report["expert_load"]``
        [expert layers, held] pairs routed to each held expert,
        ``report["pairs_dropped"]`` and ``report["layers_at_full_bound"]``
        (expert layers x steps whose load overflowed the likely rows' bound
        and ran at the worst-case one), over ``steps`` steps; with a
        multi-token-prediction module, ``report["mtp_loss"]``: its gauge
        ``mtp.loss`` is the epoch's mean of the module's own cross-entropy,
        which the fit's ``train_loss`` holds ``mtp_weight`` times beside the
        main one."""
        if not steps:
            return {}
        said = self._expert_facts(report, steps) if self.expert_layers else {}
        if self.mtp_built and "mtp_loss" in report:
            said.setdefault("gauges", {})["mtp.loss"] = float(
                np.sum(report["mtp_loss"])) / steps
        return said

    def _expert_facts(self, report: dict, steps: int) -> dict:
        load = np.asarray(report["expert_load"], np.float64)
        dropped = float(np.sum(report["pairs_dropped"]))
        overflows = float(np.sum(report["layers_at_full_bound"]))
        held = float(load.sum()) - dropped
        mean = np.maximum(load.mean(axis=1), 1e-9)
        return {
            "counters": {"experts.pairs_held": held,
                         "experts.pairs_dropped": dropped,
                         "experts.layers_at_full_bound": overflows,
                         "experts.steps_reported": steps},
            "gauges": {
                "experts.load_max_over_mean": float(
                    (load.max(axis=1) / mean).mean()),
                "experts.pairs_held_per_step": held / steps,
                "experts.likely_bound_share": 1.0 - overflows / (
                    steps * self.expert_layers)}}

    # -- pieces --------------------------------------------------------------
    def _dot(self, x, w):
        return jnp.dot(x, w.astype(self.dtype))

    def _residual(self, h, m):
        # in float32: 0.22 is not a bf16 number
        return (h.astype(jnp.float32)
                + self.residual_multiplier * m.astype(jnp.float32)
                ).astype(h.dtype)

    def _attention(self, w, y, window: int = 0, rope: bool = True):
        """``window`` > 0: the layer's queries see that many keys, their own
        position among them; ``rope`` False: no positions, whatever
        ``rope_theta``. A model with window layers names the two kinds
        (``hybridlm.attention.window`` / ``.global`` inside the scope)."""
        kind = contextlib.nullcontext() if not any(
            self.layer_windows) else obs.device_scope(
                "hybridlm.attention." + ("window" if window else "global"))
        with obs.device_scope("hybridlm.attention"), kind:
            b, t, _ = y.shape
            dh, group = self.head_dim, self.num_heads // self.num_kv_heads

            def split(z):  # [B, T, heads x Dh] -> [B, heads, T, Dh]
                return z.reshape(b, t, -1, dh).transpose(0, 2, 1, 3)

            def normed(q, k, over):  # where the family norms them, if at all
                if not self.qk_norm or self.qk_norm_over != over:
                    return q, k
                return (rms_norm(q, w["q_norm"], self.rms_eps),
                        rms_norm(k, w["k_norm"], self.rms_eps))

            # "projection": the statistic over all the heads held here,
            # before the split; "head": over each head, after it
            q, k = normed(self._dot(y, w["wq"]), self._dot(y, w["wk"]),
                          "projection")
            q, k = normed(split(q), split(k), "head")
            if self.rope_theta and rope:
                cos, sin = rope_tables(t, dh, self.rope_theta)
                q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
            # the attention's own scale is head_dim ** -0.5
            scale = self.attention_multiplier * math.sqrt(dh)
            q = q * jnp.asarray(scale, self.dtype)
            k = jnp.repeat(k, group, axis=1)
            v = jnp.repeat(split(self._dot(y, w["wv"])), group, axis=1)
            o = _attend(q, k, v, impl=self.attn_impl, axis="sp", causal=True,
                        window=window or None)
            o = o.transpose(0, 2, 1, 3).reshape(b, t, self.attention_width)
            return self._dot(o, w["wo"])

    def _conv(self, w, x):
        """Depthwise causal convolution over time and its silu, float32:
        ``out_t = bias + sum_k w[k] x_{t - (K - 1) + k}``."""
        out = w["conv_b"] + _depthwise_causal(
            x.astype(jnp.float32), w["conv_w"])
        return nn.silu(out).astype(x.dtype)

    def _mamba(self, w, u):
        with obs.device_scope("hybridlm.mamba"):
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

    def _delta_act(self, x, where: str):
        """The mixer's activation after the convolution (``where`` is
        ``"conv"``) and on the read-out's gate (``"gate"``): silu, both."""
        return nn.silu(x)

    def _delta_l2(self, x):
        """x / sqrt(sum x^2 + 1e-6) over a head's width, float32."""
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    def _delta_qkv(self, w, a):
        """(q, k [B, T, H, Dk], v [B, T, H, Dv]) of a delta-rule mixer,
        float32: through the depthwise causal convolution and its silu, q
        and k l2-normed a head, q scaled by Dk ** -0.5."""
        b, t, _ = a.shape
        heads, dk, dv = (self.delta_heads, self.delta_key_dim,
                         self.delta_value_dim)
        qkv = jnp.concatenate(
            [self._dot(a, w[name]) for name in ("wq", "wk", "wv")],
            axis=-1).astype(jnp.float32)
        qkv = self._delta_act(_depthwise_causal(qkv, w["conv_w"]), "conv")
        q, k, v = jnp.split(qkv, [heads * dk, 2 * heads * dk], axis=-1)
        q = self._delta_l2(q.reshape(b, t, heads, dk)) * dk ** -0.5
        k = self._delta_l2(k.reshape(b, t, heads, dk))
        return q, k, v.reshape(b, t, heads, dv)

    def _kda(self, w, a):
        """The ``kda`` mixer on ``a`` [B, T, D]: ``_delta``'s q, k and v,
        ``beta`` in (0, 1), a log-decay A CHANNEL bounded below
        (``kda_decay_floor`` x a sigmoid), ``ops.delta_rule.
        channel_gated_delta_rule``, the read-out normed a head and gated by
        ONE sigmoid a head before ``W_o``. Around the scan the chain runs as
        ``ops.kda_mixer``'s fused kernels on the scan's own ``[T, H x d]``
        layout wherever they take the sizes (``_kda_mixer`` says), and as
        plain ``jnp`` where not: the same arithmetic."""
        with obs.device_scope("hybridlm.delta"):
            b, t, _ = a.shape
            heads, dk, dv = (self.delta_heads, self.delta_key_dim,
                             self.delta_value_dim)
            f32 = jnp.float32
            fused = self._kda_mixer(t)[0] == "kernel"
            if fused:  # [B, T, H x d] from here to W_o: the scan's layout
                q, k, v, log_alpha = kda_mixer.operands(
                    *(self._dot(a, w[name])
                      for name in ("wq", "wk", "wv", "wf")),
                    w["conv_w"], w["A_log"], w["dt_bias"],
                    self.kda_decay_floor)
            else:
                q, k, v = (x.astype(self.dtype)
                           for x in self._delta_qkv(w, a))
                log_alpha = self.kda_decay_floor * jax.nn.sigmoid(
                    jnp.exp(w["A_log"])[:, None] * (
                        self._dot(a, w["wf"]).astype(f32) + w["dt_bias"]
                    ).reshape(b, t, heads, dk))
            beta = jax.nn.sigmoid(self._dot(a, w["wb"]).astype(f32))
            o = delta_rule.channel_gated_delta_rule(q, k, v, log_alpha, beta)
            gate = jax.nn.sigmoid(self._dot(a, w["wg"]).astype(f32))
            if fused:
                return self._dot(kda_mixer.read_out(
                    o, gate, w["gate_norm"], self.rms_eps), w["wo"])
            o = rms_norm(o.astype(f32), w["gate_norm"], self.rms_eps)
            return self._dot(
                (o * gate[..., None]).reshape(b, t, heads * dv).astype(
                    self.dtype), w["wo"])

    def _kda_mixer(self, t: int) -> tuple:
        """(``kernel`` | ``xla``, why not the kernels: "" where they run):
        what runs a ``kda`` mixer's chain around its scan on rows of ``t``
        tokens (``ops.kda_mixer.refused``: from the sizes alone)."""
        why_not = kda_mixer.refused(t, self.delta_conv, self.delta_key_dim,
                                    self.delta_value_dim)
        return ("xla" if why_not else "kernel"), why_not or ""

    def _latent_attention(self, w, y):
        """The ``mla`` mixer's training side on ``y`` [B, T, D]: K's
        position-free part and V come up from one normed latent, every head
        shares the one RoPE key, keys of ``head_dim`` stand over values of
        ``value_head_dim``; ``query_rank`` > 0: the query comes up from a
        normed latent too; ``latent_gate``: one sigmoid a head gates the
        read-out."""
        with obs.device_scope("hybridlm.attention"):
            b, t, _ = y.shape
            heads, rope, dv = self.num_heads, self.rope_head_dim, self.value_width
            nope = self.head_dim - rope
            with obs.device_scope("hybridlm.attention.query"):
                q = self._dot(y, w["wq"]) if not self.query_rank else self._dot(
                    rms_norm(self._dot(y, w["wqa"]), w["q_norm"],
                             self.rms_eps), w["wqb"])
            q = q.reshape(b, t, heads, nope + rope).transpose(0, 2, 1, 3)
            with obs.device_scope("hybridlm.attention.latent"):
                latent, k_rope = jnp.split(
                    self._dot(y, w["wkva"]), [self.latent_rank], axis=-1)
                kv = self._dot(
                    rms_norm(latent, w["kv_norm"], self.rms_eps), w["wkvb"])
            k_nope, v = jnp.split(
                kv.reshape(b, t, heads, nope + dv).transpose(0, 2, 1, 3),
                [nope], axis=-1)
            cos, sin = rope_tables(t, rope, self.rope_theta)

            def turned(x):
                # interleaved pairs (x[2i], x[2i+1]): the pairs taken apart
                # into halves, q and k alike, and the halves rotated; a
                # pair's angle is the same and q.k does not see the order
                halves = jnp.moveaxis(
                    x.reshape(x.shape[:-1] + (rope // 2, 2)), -1, -2)
                return apply_rope(halves.reshape(x.shape), cos, sin)

            # the attention's own scale is head_dim ** -0.5
            scale = self.attention_multiplier * math.sqrt(self.head_dim)
            q = jnp.concatenate([q[..., :nope], turned(q[..., nope:])],
                                axis=-1) * jnp.asarray(scale, self.dtype)
            k = jnp.concatenate([k_nope, jnp.broadcast_to(
                turned(k_rope[:, None]), (b, heads, t, rope))], axis=-1)
            o = _attend(q, k, v, impl=self.attn_impl, axis="sp", causal=True)
            o = o.transpose(0, 2, 1, 3)
            if self.latent_gate:
                gate = jax.nn.sigmoid(
                    self._dot(y, w["wg"]).astype(jnp.float32))
                o = o.astype(jnp.float32) * gate[..., None]
            return self._dot(o.reshape(b, t, heads * dv).astype(self.dtype),
                             w["wo"])

    def _delta(self, w, a):
        """The gated delta-rule mixer on ``a`` [B, T, D]: q, k, v through
        the depthwise causal convolution and its silu, q and k l2-normed a
        head (q scaled by Dk ** -0.5), ``beta`` and the decay a head from
        ``a``, ``ops.delta_rule.gated_delta_rule``, and the read-out normed
        a head and gated by silu(W_g a) before ``W_o``."""
        with obs.device_scope("hybridlm.delta"):
            b, t, _ = a.shape
            heads, dv = self.delta_heads, self.delta_value_dim
            f32 = jnp.float32
            q, k, v = self._delta_qkv(w, a)
            # in (0, 2): past 1 a step reflects (linear_allow_neg_eigval)
            beta = 2.0 * jax.nn.sigmoid(self._dot(a, w["wb"]).astype(f32))
            log_alpha = -jnp.exp(w["A_log"]) * jax.nn.softplus(
                self._dot(a, w["wa"]).astype(f32) + w["dt_bias"])
            o = delta_rule.gated_delta_rule(
                q.astype(self.dtype), k.astype(self.dtype),
                v.astype(self.dtype), log_alpha, beta)
            gate = self._delta_act(self._dot(a, w["wg"]).astype(f32), "gate")
            o = rms_norm(o.astype(f32), w["gate_norm"], self.rms_eps)
            return self._dot(
                (o.reshape(b, t, heads * dv) * gate).astype(self.dtype),
                w["wo"])

    def _gated_norm(self, w, y, z):
        """RMSNorm(y silu(z)): the gate BEFORE the norm (Mamba-2's order),
        the norm over the whole inner width (one group)."""
        gated = y.astype(jnp.float32) * nn.silu(z.astype(jnp.float32))
        return rms_norm(gated.astype(self.dtype), w["gate_norm"], self.rms_eps)

    def _short_conv(self, w, u):
        """The gated short convolution: ``W_out(C * conv(B * x))``, the
        depthwise causal convolution (no bias, no activation) and both
        gates in float32."""
        with obs.device_scope("hybridlm.conv"):
            bm, cm, x = jnp.split(
                self._dot(u, w["in_proj"]).astype(jnp.float32), 3, axis=-1)
            conv = _depthwise_causal(bm * x, w["conv_w"])
            return self._dot((cm * conv).astype(self.dtype), w["out_proj"])

    def _mlp(self, w, y):
        with obs.device_scope("hybridlm.mlp"):
            g, u = jnp.split(self._dot(y, w["w_in"]), 2, axis=-1)
            return checkpoint_name(self._dot(nn.silu(g) * u, w["w_out"]),
                                   "mlp_out")

    def _experts(self, w, y, block_input=None):
        """(this chip's part of the routed experts' result [B, T, D], what
        the layer reports: ``ops.experts.routed_experts``'s).
        ``block_input``: what the router reads in ``y``'s place
        (``router_input="block"``)."""
        with obs.device_scope("hybridlm.experts"):
            b, t, d = y.shape
            out, report = experts_op.routed_experts(
                y.reshape(b * t, d), w["router"], w.get("expert_bias"),
                w["w13"], w["w2"], first=self.first_expert,
                top_k=self.experts_per_token, scaling=self.routed_scaling,
                scope="hybridlm.experts", scoring=self.expert_scoring,
                activation=self.expert_activation,
                router_input=None if block_input is None
                else block_input.reshape(b * t, d),
                groups=self.expert_groups,
                groups_kept=self.expert_groups_kept,
                weight_eps=self.expert_weight_eps)
            report["sel"] = report["sel"].reshape(b, t, -1)
            out = out.astype(self.dtype).reshape(b, t, d)
            if self.shared_experts:
                # every token's, whole on every chip: added AFTER the combine
                with obs.device_scope("hybridlm.experts.shared"):
                    g, u = jnp.split(self._dot(y, w["shared_in"]), 2, axis=-1)
                    act = experts_op.ACTIVATIONS[self.expert_activation]
                    shared = self._dot(act(g) * u, w["shared_out"])
                    out = (out.astype(jnp.float32) + shared.astype(
                        jnp.float32)).astype(self.dtype)
            return checkpoint_name(out, "mlp_out"), report

    def _block(self, kind, ffn, w, h, window: int = 0, rope: bool = True):
        """(h after the layer, what its FFN reports: {} for a dense one).
        ``window`` and ``rope`` are an attention layer's."""
        mixer = {MAMBA: self._mamba, CONV: self._short_conv,
                 DELTA: self._delta, KDA: self._kda,
                 MLA: self._latent_attention,
                 ATTENTION: functools.partial(
                     self._attention, window=window, rope=rope)}[kind]
        # the router's input where it is the block's: h before the first norm
        routed_from = h if self.router_input == "block" else None
        post = self.norm_placement == "post"

        def normed(x, gain, here):  # "post": a sub-layer reads the stream as
            # it is and its OUTPUT is normed; "pre": its input is
            return rms_norm(x, gain, self.rms_eps) if here else x

        h = self._residual(h, normed(
            mixer(w, normed(h, w["norm1"], not post)), w["norm1"], post))
        y = normed(h, w["norm2"], not post)
        out, report = self._experts(w, y, routed_from) if ffn == EXPERTS else (
            self._mlp(w, y), {})
        return self._residual(h, normed(out, w["norm2"], post)), report

    def _head_operand(self) -> tuple:
        """(the head's float32 matrix, its axis that meets D): the tied
        embedding [V, D], or the model's own head [D, V]."""
        return (self.embed, 1) if self.tied_head else (self.head_w, 0)

    def head(self, h):
        """Logits, float32, from the final norm's output (the tied head, or
        the model's own)."""
        w = self.embed.T if self.tied_head else self.head_w
        return jnp.dot(h, w.astype(self.dtype),
                       preferred_element_type=jnp.float32
                       ) / self.logits_scaling

    # -- surfaces ------------------------------------------------------------
    def _recomputed_block(self):
        """``_block``, recomputed in the backward pass but for what the
        model's layers keep (``remat``)."""
        keeps = (REMAT_KEEPS + (EXPERT_KEEPS if self.expert_layers else ())
                 + (KDA_KEEPS if KDA in self.layer_types else ()))
        return jax.checkpoint(
            self._block, static_argnums=(0, 1, 4, 5),
            policy=jax.checkpoint_policies.save_only_these_names(*keeps),
        ) if self.remat else self._block

    def _stream(self, tokens):
        """(the stream the final norm READS [B, T, D], the expert layers'
        reports, a list)."""
        h = (self.embedding_multiplier * self.embed[tokens]).astype(self.dtype)
        block = self._recomputed_block()
        reports = []
        for kind, ffn, w, window, rope in zip(
                self.layer_types, self.ffn_kinds, self.layers,
                self.layer_windows, self.layer_ropes):
            h, report = block(kind, ffn, w, h, window, rope)
            if report:
                reports.append(report)
        return h, reports

    @staticmethod
    def _stacked(reports: list) -> dict:
        return {key: jnp.stack([r[key] for r in reports])
                for key in (reports[0] if reports else ())}

    def _states(self, tokens):
        """(the final norm's output [B, T, D], the expert layers' reports
        stacked: {} without expert layers)."""
        h, reports = self._stream(tokens)
        return (rms_norm(h, self.final_norm, self.rms_eps),
                self._stacked(reports))

    def _mtp(self, h, x):
        """The multi-token-prediction module on the stream ``h`` [B, T, D]
        the final norm reads and the row ``x`` [B, T + 1]: (the mean
        cross-entropy of token i + 2 from row i over the rows that have one,
        the state its head read [B, T, D], its block's report). Row T - 1
        has no target: it keeps its place, so no kernel's grid changes, and
        weighs 0."""
        w = self.mtp
        b, t = x.shape[0], x.shape[1] - 1
        with obs.device_scope("hybridlm.mtp"):
            with obs.device_scope("hybridlm.mtp.combine"):
                ahead = (self.embedding_multiplier
                         * self.embed[x[:, 1:]]).astype(self.dtype)
                u = self._dot(jnp.concatenate(
                    [rms_norm(ahead, w["enorm"], self.rms_eps),
                     rms_norm(h, w["hnorm"], self.rms_eps)], axis=-1),
                    w["eh_proj"])
            g, report = self._recomputed_block()(*self.mtp_kinds, w, u, 0, True)
            g = rms_norm(g, w["final_norm"], self.rms_eps)
            targets = jnp.pad(x[:, 2:], ((0, 0), (0, 1)))
            weight = jnp.broadcast_to(
                (jnp.arange(t) < t - 1) / (b * max(t - 1, 1)), (b, t)
            ).astype(jnp.float32)
            loss, _ = chunked_cross_entropy(
                g, *self._head_operand(), targets, self.loss_chunk,
                "hybridlm.mtp.loss", scale=1.0 / self.logits_scaling,
                weight=weight)
        return loss, g, report

    def hidden_states(self, tokens):
        """The final norm's output [B, T, D]: what the head reads."""
        return self._states(tokens)[0]

    def __call__(self, tokens):
        """Logits [B, T, V]."""
        return self.head(self.hidden_states(tokens))

    def placed_by_load(self, rng, batches):
        """(this model with its experts PLACED by the load its seeded routers
        give on ``batches`` (int32 [B, T+1] each), every expert layer's
        share of the even load before and after): what an expert-parallel
        group does at set-up about a router that takes NO bias, whose load
        no rule evens. Layer after layer, first to last (where a layer's
        experts sit decides which of them add to the stream the next
        layer's router reads): every token's choice under the parameters
        ``init(rng, ...)`` gives, ``ops.experts.place`` over the group's
        ``experts_total / experts_held`` chips, and the router's columns
        re-ordered so that this chip's slots score the experts placed
        here. The experts' own weights are seeded alike, so which of them
        a slot's weights "were" says nothing: the placement is a
        permutation of the router's columns, ``expert_placement``, that
        ``init`` applies, and the same ``rng`` then gives every caller the
        placed parameters. One compile of the forward pass, a run a layer
        and batch."""
        chips = self.experts_total // max(self.experts_held, 1)
        here = self.first_expert // max(self.experts_held, 1)
        if (not self.expert_layers or self.expert_placement
                or self.experts_total % self.experts_held
                or self.first_expert % self.experts_held):
            raise ValueError(
                "placed_by_load places the experts of an unplaced model "
                "whose share is one of experts_total / experts_held equal "
                "chips'")
        params = jax.jit(
            lambda r: self.init(r, batches[0], None, method="loss"))(rng)
        chosen = jax.jit(lambda p, x: self.apply(
            p, x, None, True, method="loss")[1]["routing"])
        names = [f"layer_{i}" for i, ffn in enumerate(self.ffn_kinds)
                 if ffn == EXPERTS]
        slots = slice(here * self.experts_held, (here + 1) * self.experts_held)
        orders, before, after = [], [], []
        for layer, name in enumerate(names):
            loads = sum(np.bincount(
                np.asarray(chosen(params, x)[layer]).ravel(),
                minlength=self.experts_total) for x in batches)
            order = experts_op.place(loads, chips)
            orders.append(order)
            before.append(float(loads[slots].sum() * chips / loads.sum()))
            after.append(float(
                loads[list(order[slots])].sum() * chips / loads.sum()))
            w = params["params"][name]
            params = {"params": {**params["params"], name: {
                **w, "router": w["router"][:, np.asarray(order)]}}}
        return self.clone(expert_placement=tuple(orders)), before, after

    def loss(self, x, y=None, with_states=False):
        """Mean next-token cross-entropy on ``x`` int32 [B, T+1] (inputs
        ``x[:, :-1]``, targets ``x[:, 1:]``; ``y`` is not used). Returns
        ``(loss, aux)``. With expert layers ``aux`` holds ``train_report``'s
        three: ``expert_load`` [expert layers, held], ``pairs_dropped`` and
        ``layers_at_full_bound``.
        ``with_states`` adds ``hidden`` [B, T, D], the state the head read,
        and ``routing`` int32 [expert layers, B, T, k], every token's
        choice (for a comparison; not for a fit, whose evaluation would
        average them). With a multi-token-prediction module the loss is the
        main one + ``mtp_weight`` x the module's, ``aux["mtp_loss"]`` is the
        module's alone (``train_report``'s fourth), its block's report is
        the LAST of the expert layers', and ``with_states`` adds
        ``mtp_hidden``, the state the module's head read."""
        stream, reports = self._stream(x[:, :-1])
        h = rms_norm(stream, self.final_norm, self.rms_eps)
        loss, _ = chunked_cross_entropy(
            h, *self._head_operand(), x[:, 1:], self.loss_chunk,
            "hybridlm.loss", scale=1.0 / self.logits_scaling)
        aux = {}
        if self.mtp_built:
            aux["mtp_loss"], ahead, report = self._mtp(stream, x)
            loss = loss + self.mtp_weight * aux["mtp_loss"]
            if report:
                reports.append(report)
        reports = self._stacked(reports)
        if reports:
            aux.update(expert_load=reports["load"],
                       pairs_dropped=reports["dropped"].sum(),
                       layers_at_full_bound=reports["full_bound"].sum())
        if with_states:
            aux["hidden"] = h
            if self.mtp_built:
                aux["mtp_hidden"] = ahead
            if reports:
                aux["routing"] = reports["sel"]
        return loss, aux


class RoutedHybridLM(HybridLM):
    """``HybridLM`` under the name a configuration with expert layers asks
    for (``config["model"]["class"]``): a program from before the experts
    FFN kind has ``HybridLM`` and not this name, and a benchmark that asks
    for it there leaves at once instead of failing inside a fit."""


class LatentDeltaHybridLM(HybridLM):
    """``HybridLM`` under the name a configuration with ``kda`` and ``mla``
    layers asks for (``config["model"]["class"]``), as ``RoutedHybridLM``
    and for its reason: a program from before those mixer kinds has no such
    name, and a benchmark that asks for it there leaves at once."""


class LatentMTPHybridLM(HybridLM):
    """``HybridLM`` under the name a configuration with a low-rank-query
    ``mla`` mixer in every layer and a multi-token-prediction module asks for
    (``config["model"]["class"]``), as ``RoutedHybridLM`` and for its reason:
    a program from before them has no such name, and a benchmark that asks
    for it there leaves at once."""


class DeltaHybridLM(HybridLM):
    """``HybridLM`` under the name a configuration with delta-rule layers
    asks for (``config["model"]["class"]``), as ``RoutedHybridLM`` and for
    its reason: a program from before the ``delta`` mixer kind has no such
    name, and a benchmark that asks for it there leaves at once, before any
    actor is started, instead of failing inside a fit."""


def hybridlm_optimizer(learning_rate: float = 3e-4, b1: float = 0.9,
                       b2: float = 0.95, weight_decay: float = 0.1,
                       warmup_steps: int = 0, expert_bias_rate: float = 0.0):
    """AdamW as LM pre-training runs it: decay on the parameters with two or
    more axes only (the matrices, the embedding, the convolution's taps;
    not on norm gains, ``A_log``, ``D``, ``dt_bias`` or the convolution's
    bias), float32 moments. ``warmup_steps``: the rate climbs linearly to
    ``learning_rate`` over that many steps (step i runs at (i + 1) /
    warmup_steps of it); 0: no schedule.

    ``expert_bias_rate``: every ``expert_bias`` leaves AdamW for the
    BALANCING RULE, ``b_e -= rate x excess_e`` (auxiliary-loss-free
    balancing, arXiv:2408.15664, with the error itself in place of its
    sign: its step shrinks as the load evens out). ``excess_e`` is what an
    expert layer hands back as the bias's gradient: the pairs that chose
    expert e over the even share, less 1 (``ops.experts.route``). 0: the
    biases stay under AdamW, which then moves them by the rate x the
    excess's sign, more or less. A model WITHOUT such leaves (a router
    that takes no bias: the softmax-over-the-selected rule) gives the rule
    nothing to move: the optimizer then is AdamW alone, and says so once,
    at ``init``."""
    import optax

    if not warmup_steps and not expert_bias_rate:
        return looplm_optimizer(learning_rate, b1, b2, weight_decay)
    rate = learning_rate if not warmup_steps else (
        lambda count: learning_rate * jnp.minimum(
            1.0, (count + 1) / warmup_steps))
    adamw = looplm_optimizer(rate, b1, b2, weight_decay)
    if not expert_bias_rate:
        return adamw

    def labels(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, _: "balance" if getattr(
                path[-1], "key", None) == "expert_bias" else "adamw", params)

    both = optax.multi_transform(
        {"adamw": adamw, "balance": optax.sgd(expert_bias_rate)}, labels)

    def init(params):
        if "balance" not in jax.tree.leaves(labels(params)):
            logging.getLogger(__name__).warning(
                "hybridlm_optimizer(expert_bias_rate=%s): the model has no "
                "expert_bias leaf, so the balancing rule moves nothing",
                expert_bias_rate)
        return both.init(params)

    return optax.GradientTransformation(init, both.update)
