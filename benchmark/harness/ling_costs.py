"""Operations and bytes that a training step of the KDA / latent-attention
hybrid with routed experts (``bailing_hybrid``: ``layer_group_size`` - 1 KDA
layers to one MLA layer, a leading dense SwiGLU, then routed experts of which
this chip holds a share beside a shared expert) and its kernels NEED, from
shapes: what the algorithm has to do, not what an implementation happens to
do (no recomputation, no masked-out work, no row past a group). The
configuration names this module under ``model.costs``; the ``lmpretrain``
drivers call ``step_flops``, ``kernels`` and ``reader_values`` with the
configuration as run. Composed from ``moe_costs`` (the grouped products a
pair, the program's word on the load) and ``delta_costs``'s yardstick (the
delta rule counted as its RECURRENCE, 6 Dk Dv FLOPs a token and head
forward, whatever implements it), with two things of its own: the decay a
token and head is a VECTOR [Dk] (read, and its gradient written, at that
width), and the flash kernels run keys of ``qk_nope + qk_rope`` over values
of ``v_head_dim``."""

from __future__ import annotations

from . import lm_costs, moe_costs
from .moe_costs import note_fence  # noqa: F401 - the driver's surface


def _dims(config: dict) -> dict:
    share = config.get("share", {})
    first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
    mla = sum((layer + 1) % config["layer_group_size"] == 0
              for layer in range(first, first + depth))
    dense = config["first_k_dense_replace"]
    held = config["num_experts"]
    return {
        "hidden": config["hidden_size"], "vocab": config["vocab_size"],
        "dense_ffn": config["intermediate_size"],
        "expert_ffn": config["moe_intermediate_size"],
        "shared_ffn": config["num_shared_experts"]
        * config["moe_shared_expert_intermediate_size"],
        "heads": config["num_attention_heads"],
        "dk": config["head_dim"], "dv": config["head_dim"],
        "taps": config["short_conv_kernel_size"],
        "key": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "value": config["v_head_dim"],
        "latent": config["kv_lora_rank"],
        "kda": depth - mla, "mla": mla,
        "dense_layers": dense, "expert_layers": depth - dense,
        "held": held, "total": share.get("experts_total", held),
        "per_token": config["num_experts_per_tok"],
    }


def uniform_pairs(config: dict, tokens: int) -> int:
    """Pairs an expert layer routes here when the load is even."""
    d = _dims(config)
    return tokens * d["per_token"] * d["held"] // d["total"]


def delta_fwd(batch: int, t: int, heads: int, dk: int, dv: int,
              itemsize: int) -> dict:
    """One KDA layer's forward recurrence over [batch, t]: 6 Dk Dv FLOPs a
    token and head; reads q, k, v, the decay [Dk] and beta and writes o
    once, at the operands' width."""
    rows = batch * t * heads
    return {"flops": rows * 6 * dk * dv,
            "bytes": rows * (3 * dk + 2 * dv + 1) * itemsize}


def delta_bwd(batch: int, t: int, heads: int, dk: int, dv: int,
              itemsize: int) -> dict:
    """The backward pass of one such call: twice the forward's FLOPs; reads
    q, k, v, the decay, beta and do once, writes the five gradients once
    (the decay's at [Dk])."""
    rows = batch * t * heads
    return {"flops": 2 * rows * 6 * dk * dv,
            "bytes": rows * ((3 * dk + 2 * dv + 1)
                             + (3 * dk + dv + 1)) * itemsize}


def flash_fwd(batch: int, heads: int, t: int, key: int, value: int,
              itemsize: int) -> dict:
    """``lm_costs.flash_fwd`` at two widths: QK^T over ``key`` and PV over
    ``value``, a kept pair; q, k read at ``key``, v read and o written at
    ``value``, once."""
    rows, pairs = batch * heads, lm_costs._causal_pairs(t)
    return {"flops": rows * 2 * (key + value) * pairs,
            "bytes": rows * (2 * t * (key + value) * itemsize + 2 * t * 4)}


def flash_bwd(batch: int, heads: int, t: int, key: int, value: int,
              itemsize: int) -> dict:
    """``lm_costs.flash_bwd`` at two widths: dV = P^T dO and dP = dO V^T
    over ``value``, dQ = dS K and dK = dS^T Q over ``key``; reads q, k (key),
    v, dO (value) and the row statistics, writes dq, dk (key) and dv
    (value), once."""
    rows, pairs = batch * heads, lm_costs._causal_pairs(t)
    return {"flops": rows * 4 * (key + value) * pairs,
            "bytes": rows * (t * (4 * key + 3 * value) * itemsize + 2 * t * 4)}


def step_flops(config: dict, batch: int, t: int) -> dict:
    """Model FLOPs of one training step, forward + backward = 3 x forward:
    ``layers`` (6 x matrix parameters x tokens: both mixers' projections and
    gates, the dense SwiGLU, the routers, the shared expert every token
    passes; the convolution's taps beside them), ``experts`` (6 x an
    expert's parameters x the UNIFORM share of the pairs), ``delta`` (the
    recurrence's), ``attention`` (causal, keys and values at their own
    widths), ``head`` (the untied head, once); recomputation does not
    count."""
    d = _dims(config)
    h = d["hidden"]
    keys, values = d["heads"] * d["dk"], d["heads"] * d["dv"]
    kda = (h * (3 * keys + values + 2 * d["heads"]) + values * h
           + d["taps"] * (2 * keys + values))
    mla = (h * d["heads"] * d["key"] + h * (d["latent"] + d["rope"])
           + d["latent"] * d["heads"] * (d["nope"] + d["value"])
           + h * d["heads"] + d["heads"] * d["value"] * h)
    ffn = (d["dense_layers"] * 3 * h * d["dense_ffn"]
           + d["expert_layers"] * (h * d["total"] + 3 * h * d["shared_ffn"]))
    tokens = batch * t
    parts = {
        "layers": 6 * (d["kda"] * kda + d["mla"] * mla + ffn) * tokens,
        "experts": d["expert_layers"] * 6 * 3 * h * d["expert_ffn"]
        * batch * uniform_pairs(config, t),
        "delta": 3 * d["kda"] * delta_fwd(
            batch, t, d["heads"], d["dk"], d["dv"], 2)["flops"],
        "attention": 3 * d["mla"] * flash_fwd(
            batch, d["heads"], t, d["key"], d["value"], 2)["flops"],
        "head": 6 * h * d["vocab"] * tokens,
    }
    parts["total"] = sum(parts.values())
    return parts


def kernels(config: dict, batch: int, t: int, itemsize: int = 2) -> dict:
    """Needed work, for the roofline readers: the flash kernels a call (32
    heads, keys of 192 over values of 128), the delta rule's forward and
    backward of one KDA layer (``layers``: how many a step), and the grouped
    products PER PAIR with the weights' bytes a step and layer."""
    d = _dims(config)
    scan = (batch, t, d["heads"], d["dk"], d["dv"], itemsize)
    flash = (batch, d["heads"], t, d["key"], d["value"], itemsize)
    pair = moe_costs.gmm_pair(d["hidden"], d["expert_ffn"], itemsize)
    return {
        "flash_fwd": {"cost": flash_fwd(*flash)},
        "flash_bwd": {"cost": flash_bwd(*flash)},
        "delta_fwd": {"cost": delta_fwd(*scan), "layers": d["kda"]},
        "delta_bwd": {"cost": delta_bwd(*scan), "layers": d["kda"]},
        "moe_gmm": {
            "per_pair": {k: pair["fwd"][k] + pair["bwd"][k]
                         for k in ("flops", "bytes")},
            "weights_per_layer_step": moe_costs.gmm_weights(
                d["hidden"], d["expert_ffn"], d["held"], itemsize),
            "layers": d["expert_layers"]},
    }


def reader_values(config: dict, batch: int, t: int) -> dict:
    """What the expert layer's readers need, as ``moe_costs.reader_values``
    gives it (the axes of the layer's arrays, the program's own count of
    the pairs and steps IN THE TRACED STRETCH, the newest epoch's gauges);
    the delta rule's and the mixers' readers go by the program's scopes and
    need nothing of the shapes."""
    d = _dims(config)
    tokens = batch * t
    try:
        from raydp_tpu.ops import experts

        rows = experts.row_bound_for(tokens * d["per_token"])
    except ImportError:
        rows = None
    return {
        "moe_axes": {"tokens": tokens, "per_token": d["per_token"],
                     "total": d["total"], "held": d["held"],
                     "hidden": d["hidden"], "width": d["expert_ffn"],
                     "rows": rows},
        "moe_pairs_in_trace": moe_costs._over_the_stretch(
            "model.experts.pairs_held"),
        "moe_steps_reported_in_trace": moe_costs._over_the_stretch(
            "model.experts.steps_reported"),
        "moe_load_max_over_mean": moe_costs._program_value(
            "model.experts.load_max_over_mean"),
        "moe_pairs_dropped": moe_costs._program_value(
            "model.experts.pairs_dropped"),
    }
