"""Operations and bytes that a training step of the window-and-global routed
LM (``smallthinker``: every layer grouped-query attention with an explicit
head size under routed ReGLU experts of which this chip holds a share; a
layer sees all keys or a window of them) and its kernels NEED, from shapes:
what the algorithm has to do, not what an implementation happens to do (no
recomputation, no masked-out work, no row past a group, NO PAIR A WINDOW
HIDES). The configuration names this module under ``model.costs``; the
``lmpretrain`` drivers call ``step_flops``, ``kernels`` and ``reader_values``
with the configuration as run.

A window layer's (query, key) pairs over ``T`` positions at window ``W`` are
``W (W + 1) / 2 + (T - W) W``: the first ``W`` queries see what causality
leaves them, every later one ``W`` keys, its own position among them. The
global layer's are ``T (T + 1) / 2``. ``step_flops`` counts the experts AT
THE UNIFORM SHARE, as ``moe_costs`` does and for its reason."""

from __future__ import annotations

from . import lm_costs, moe_costs


def window_pairs(t: int, window: int) -> int:
    w = min(window, t)
    return w * (w + 1) // 2 + (t - w) * w


def _dims(config: dict) -> dict:
    share = config.get("share", {})
    first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
    windowed = list(config["sliding_window_layout"][first:first + depth])
    held = config["moe_num_primary_experts"]
    return {
        "hidden": config["hidden_size"], "vocab": config["vocab_size"],
        "expert_ffn": config["moe_ffn_hidden_size"],
        "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["head_dim"],
        "window": config["sliding_window_size"],
        "window_layers": sum(1 for w in windowed if w),
        "global_layers": sum(1 for w in windowed if not w),
        "layers": depth,
        "held": held, "total": share.get("experts_total", held),
        "per_token": config["moe_num_active_primary_experts"],
    }


def uniform_pairs(config: dict, tokens: int) -> int:
    """Pairs an expert layer routes here when the load is even."""
    d = _dims(config)
    return tokens * d["per_token"] * d["held"] // d["total"]


def flash_window_fwd(batch: int, heads: int, t: int, window: int,
                     head_dim: int, itemsize: int) -> dict:
    """One window forward call over [batch, heads, t, head_dim]: QK^T and PV,
    2 x head_dim operations each per pair the window keeps. Bytes as the
    causal call's: q, k, v read and o written once, the two float32 row
    statistics written."""
    rows = batch * heads
    return {"flops": rows * 4 * head_dim * window_pairs(t, window),
            "bytes": lm_costs.flash_fwd(batch, heads, t, head_dim,
                                        itemsize)["bytes"]}


def flash_window_bwd(batch: int, heads: int, t: int, window: int,
                     head_dim: int, itemsize: int) -> dict:
    """The backward pass of one such call (the dq and the dk/dv kernel
    together): four products of 2 x head_dim operations a kept pair; bytes
    as the causal pass's."""
    rows = batch * heads
    return {"flops": rows * 8 * head_dim * window_pairs(t, window),
            "bytes": lm_costs.flash_bwd(batch, heads, t, head_dim,
                                        itemsize)["bytes"]}


def step_flops(config: dict, batch: int, t: int) -> dict:
    """Model FLOPs of one training step, forward + backward = 3 x forward:
    ``layers`` (6 x matrix parameters x tokens: q, k, v, o and the router),
    ``experts`` (6 x an expert's parameters x the UNIFORM share of the
    pairs), ``attention`` (the pairs the layers NEED: causal for a global
    layer, the window's for a window layer), ``head`` (the untied head,
    once); recomputation does not count."""
    d = _dims(config)
    h, wide = d["hidden"], d["q_heads"] * d["head_dim"]
    attention = 2 * h * wide + 2 * h * d["kv_heads"] * d["head_dim"]
    tokens = batch * t
    pairs = (d["global_layers"] * (t * (t + 1) // 2)
             + d["window_layers"] * window_pairs(t, d["window"]))
    parts = {
        "layers": 6 * d["layers"] * (attention + h * d["total"]) * tokens,
        "experts": d["layers"] * 6 * 3 * h * d["expert_ffn"]
        * batch * uniform_pairs(config, t),
        "attention": 3 * 4 * wide * pairs * batch,
        "head": 6 * h * d["vocab"] * tokens,
    }
    parts["total"] = sum(parts.values())
    return parts


def kernels(config: dict, batch: int, t: int, itemsize: int = 2) -> dict:
    """Needed work, for the roofline readers: the global layer's flash calls
    (``flash_fwd`` / ``flash_bwd``: causal, over the query heads: K and V
    are repeated to them in HBM), the window layers' (``flash_window_fwd`` /
    ``flash_window_bwd``), and the grouped products PER PAIR forward and
    backward with the weights' bytes a step and layer."""
    d = _dims(config)
    pair = moe_costs.gmm_pair(d["hidden"], d["expert_ffn"], itemsize)
    args = (batch, d["q_heads"], t)
    return {
        "flash_fwd": {"cost": lm_costs.flash_fwd(
            *args, d["head_dim"], itemsize)},
        "flash_bwd": {"cost": lm_costs.flash_bwd(
            *args, d["head_dim"], itemsize)},
        "flash_window_fwd": {"cost": flash_window_fwd(
            *args, d["window"], d["head_dim"], itemsize)},
        "flash_window_bwd": {"cost": flash_window_bwd(
            *args, d["window"], d["head_dim"], itemsize)},
        "moe_gmm": {
            "per_pair": {k: pair["fwd"][k] + pair["bwd"][k]
                         for k in ("flops", "bytes")},
            "weights_per_layer_step": moe_costs.gmm_weights(
                d["hidden"], d["expert_ffn"], d["held"], itemsize),
            "layers": d["layers"]},
    }


def reader_values(config: dict, batch: int, t: int) -> dict:
    """The program's own word on the experts' load, as ``moe_costs.
    reader_values`` gives it (the routed driver notes the traced stretch's
    fences there): the pairs computed here and the steps reported in the
    traced stretch, and the gauges of the newest epoch fenced."""
    return {
        "moe_pairs_in_trace": moe_costs._over_the_stretch(
            "model.experts.pairs_held"),
        "moe_steps_reported_in_trace": moe_costs._over_the_stretch(
            "model.experts.steps_reported"),
        "moe_load_max_over_mean": moe_costs._program_value(
            "model.experts.load_max_over_mean"),
        "moe_pairs_dropped": moe_costs._program_value(
            "model.experts.pairs_dropped"),
    }
