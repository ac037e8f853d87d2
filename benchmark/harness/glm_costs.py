"""Operations and bytes that a training step of the latent-attention model
with a multi-token-prediction module (``glm4_moe_lite``: DeepSeek-V3's block:
latent attention with a low-rank query in EVERY layer, a leading dense
SwiGLU, then routed experts of which this chip holds a share beside a shared
expert; one module of ``eh_proj``, two norms and one more such block, whose
loss runs the shared head a second time) and its kernels NEED, from shapes:
what the algorithm has to do, not what an implementation happens to do (no
recomputation, no masked-out work, no row past a group; the module's last
row, which weighs 0, IS counted: the published objective runs T - 1 of T rows
and the difference is 1 in 8192). The configuration names this module under
``model.costs``; the ``lmpretrain`` drivers call ``step_flops``, ``kernels``
and ``reader_values`` with the configuration as run. Composed from
``moe_costs`` (the grouped products a pair, the program's word on the load)
and ``ling_costs``' flash pair at two widths (here keys of ``qk_nope +
qk_rope`` = 256 over values of ``v_head_dim`` = 256, 20 heads, a call a
block: the layers' and the module's)."""

from __future__ import annotations

from . import ling_costs, moe_costs
from .moe_costs import note_fence  # noqa: F401 - the driver's surface


def _dims(config: dict) -> dict:
    share = config.get("share", {})
    first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
    dense = sum(layer < config["first_k_dense_replace"]
                for layer in range(first, first + depth))
    held = config["n_routed_experts"]
    # lambda is the configuration's as run (``model.kwargs``): config.json
    # has no key for it, and without a weight the program builds no module
    weight = config.get("model", {}).get("kwargs", {}).get("mtp_weight", 0.0)
    modules = config.get("num_nextn_predict_layers", 0) if weight else 0
    return {
        "hidden": config["hidden_size"], "vocab": config["vocab_size"],
        "dense_ffn": config["intermediate_size"],
        "expert_ffn": config["moe_intermediate_size"],
        "shared_ffn": config["n_shared_experts"]
        * config["moe_intermediate_size"],
        "heads": config["num_attention_heads"],
        "key": config["qk_nope_head_dim"] + config["qk_rope_head_dim"],
        "nope": config["qk_nope_head_dim"],
        "rope": config["qk_rope_head_dim"], "value": config["v_head_dim"],
        "q_latent": config["q_lora_rank"], "latent": config["kv_lora_rank"],
        "modules": modules,
        # the module's block is one more latent-attention layer over experts
        "mla": depth + modules, "dense_layers": dense,
        "expert_layers": depth - dense + modules,
        "held": held, "total": share.get("experts_total", held),
        "per_token": config["num_experts_per_tok"],
    }


def uniform_pairs(config: dict, tokens: int) -> int:
    """Pairs an expert layer routes here when the load is even."""
    d = _dims(config)
    return tokens * d["per_token"] * d["held"] // d["total"]


def mixer_params(d: dict) -> int:
    """A latent-attention mixer's matrices: the query down and up, the
    key/value down (with the one RoPE key) and up, the read-out."""
    h, heads = d["hidden"], d["heads"]
    return (h * d["q_latent"] + d["q_latent"] * heads * d["key"]
            + h * (d["latent"] + d["rope"])
            + d["latent"] * heads * (d["nope"] + d["value"])
            + heads * d["value"] * h)


def step_flops(config: dict, batch: int, t: int) -> dict:
    """Model FLOPs of one training step, forward + backward = 3 x forward:
    ``layers`` (6 x matrix parameters x tokens: the mixers' projections, the
    dense SwiGLU, the routers, the shared expert every token passes; the
    module's block and ``eh_proj`` among them), ``experts`` (6 x an expert's
    parameters x the UNIFORM share of the pairs, the module's layer too),
    ``attention`` (causal, a call a block), ``head`` (the untied head, once a
    loss: twice with the module); ``mtp`` is the module's part of all four,
    for the reader, and is NOT added to ``total`` a second time.
    Recomputation does not count."""
    d = _dims(config)
    h, tokens = d["hidden"], batch * t
    expert_layer = mixer_params(d) + h * d["total"] + 3 * h * d["shared_ffn"]
    matrices = (d["mla"] * mixer_params(d)
                + d["dense_layers"] * 3 * h * d["dense_ffn"]
                + d["expert_layers"] * (h * d["total"] + 3 * h * d["shared_ffn"])
                + d["modules"] * 2 * h * h)
    experts = 6 * 3 * h * d["expert_ffn"] * batch * uniform_pairs(config, t)
    attention = 3 * ling_costs.flash_fwd(
        batch, d["heads"], t, d["key"], d["value"], 2)["flops"]
    head = 6 * h * d["vocab"] * tokens
    parts = {
        "layers": 6 * matrices * tokens,
        "experts": d["expert_layers"] * experts,
        "attention": d["mla"] * attention,
        "head": (1 + d["modules"]) * head,
    }
    parts["total"] = sum(parts.values())
    parts["mtp"] = d["modules"] * (
        6 * (expert_layer + 2 * h * h) * tokens + experts + attention + head)
    return parts


def kernels(config: dict, batch: int, t: int, itemsize: int = 2) -> dict:
    """Needed work, for the roofline readers: the flash kernels a call (20
    heads, keys of 256 over values of 256; ``layers``: the calls a step, the
    module's among them), and the grouped products PER PAIR with the weights'
    bytes a step and layer."""
    d = _dims(config)
    flash = (batch, d["heads"], t, d["key"], d["value"], itemsize)
    pair = moe_costs.gmm_pair(d["hidden"], d["expert_ffn"], itemsize)
    return {
        "flash_fwd": {"cost": ling_costs.flash_fwd(*flash), "layers": d["mla"]},
        "flash_bwd": {"cost": ling_costs.flash_bwd(*flash), "layers": d["mla"]},
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
    the scopes' readers go by the program's map and need nothing of the
    shapes."""
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
