"""Operations and bytes that a training step of the delta-rule hybrid LM
(``olmo_hybrid``: gated delta-rule linear-attention layers and full-attention
layers without positions, a dense SwiGLU in each, of which this chip holds a
share of every mixer's HEADS) and its scan NEED, from shapes: what the
algorithm has to do, not what an implementation happens to do (no
recomputation, no masked-out work). The configuration names this module under
``model.costs``; the ``lmpretrain`` driver calls ``step_flops``, ``kernels``
and ``reader_values`` with the configuration as run.

The delta rule is counted as its RECURRENCE, whatever implements it, so that
a later kernel is read by the same yardstick: a token and held head decays
and erases (``S k``: 2 Dk Dv), writes (the rank-one update: 2 Dk Dv) and
reads out (``S q``: 2 Dk Dv): 6 Dk Dv FLOPs forward, twice that backward. The
chunked form the program runs (``raydp_tpu/ops/delta_rule.py``) does about
1.8 times these at chunks of 64 (the chunk's score matrices and its
triangular solve beside the three state products); they are not needed
work."""

from __future__ import annotations

from . import lm_costs


def _dims(config: dict) -> dict:
    first = config.get("share", {}).get("first_layer", 0)
    kinds = list(config["layer_types"][
        first:first + config["num_hidden_layers"]])
    heads = config["num_attention_heads"]
    return {
        "hidden": config["hidden_size"], "vocab": config["vocab_size"],
        "ffn": config["intermediate_size"],
        "q_heads": heads, "kv_heads": config["num_key_value_heads"],
        "head_dim": config.get("head_dim") or config["hidden_size"] // heads,
        "heads": config["linear_num_key_heads"],
        "dk": config["linear_key_head_dim"],
        "dv": config["linear_value_head_dim"],
        "conv": config["linear_conv_kernel_dim"],
        "delta": kinds.count("linear_attention"),
        "attention": kinds.count("full_attention"),
    }


def delta_fwd(batch: int, t: int, heads: int, dk: int, dv: int,
              itemsize: int) -> dict:
    """One layer's forward recurrence over [batch, t]: 6 Dk Dv FLOPs a
    token and head; reads q, k, v, alpha and beta and writes o once, at the
    operands' width."""
    rows = batch * t * heads
    return {"flops": rows * 6 * dk * dv,
            "bytes": rows * (2 * dk + 2 * dv + 2) * itemsize}


def delta_bwd(batch: int, t: int, heads: int, dk: int, dv: int,
              itemsize: int) -> dict:
    """The backward pass of one such call: twice the forward's FLOPs; reads
    q, k, v, alpha, beta and do once, writes the five gradients once."""
    rows = batch * t * heads
    return {"flops": 2 * rows * 6 * dk * dv,
            "bytes": rows * ((2 * dk + 2 * dv + 2)
                             + (2 * dk + dv + 2)) * itemsize}


def step_flops(config: dict, batch: int, t: int) -> dict:
    """Model FLOPs of one training step, forward + backward = 3 x forward:
    ``layers`` (6 x matrix parameters x tokens, the convolution's taps
    beside them), ``delta`` (the recurrence's), ``attention`` (causal, over
    the held heads), ``head`` (the untied head, once); recomputation does
    not count."""
    d = _dims(config)
    h, ffn = d["hidden"], 3 * d["hidden"] * d["ffn"]
    keys, values = d["heads"] * d["dk"], d["heads"] * d["dv"]
    delta = (h * (2 * keys + 2 * values + 2 * d["heads"]) + values * h
             + d["conv"] * (2 * keys + values) + ffn)
    wide = d["q_heads"] * d["head_dim"]
    attention = (2 * h * wide + 2 * h * d["kv_heads"] * d["head_dim"] + ffn)
    tokens = batch * t
    parts = {
        "layers": 6 * (d["delta"] * delta + d["attention"] * attention)
        * tokens,
        "attention": 3 * d["attention"] * 4 * wide
        * (t * (t + 1) // 2) * batch,
        "head": 6 * h * d["vocab"] * tokens,
        "delta": 3 * d["delta"] * delta_fwd(
            batch, t, d["heads"], d["dk"], d["dv"], 2)["flops"],
    }
    parts["total"] = sum(parts.values())
    return parts


def kernels(config: dict, batch: int, t: int, itemsize: int = 2) -> dict:
    """Needed work a call, for the roofline readers: the flash kernels over
    the held heads of the full-attention layer (as many K/V heads as query
    heads: nothing is repeated), and the delta rule's forward and backward
    of one layer (``layers``: how many a step)."""
    d = _dims(config)
    scan = (batch, t, d["heads"], d["dk"], d["dv"], itemsize)
    return {
        "flash_fwd": {"cost": lm_costs.flash_fwd(
            batch, d["q_heads"], t, d["head_dim"], itemsize)},
        "flash_bwd": {"cost": lm_costs.flash_bwd(
            batch, d["q_heads"], t, d["head_dim"], itemsize)},
        "delta_fwd": {"cost": delta_fwd(*scan), "layers": d["delta"]},
        "delta_bwd": {"cost": delta_bwd(*scan), "layers": d["delta"]},
    }


def reader_values(config: dict, batch: int, t: int) -> dict:
    """The delta rule's readers go by the program's own scope
    (``harness/scopes.py``), not by shapes: nothing of the shapes is
    needed."""
    return {}
