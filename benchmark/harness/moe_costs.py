"""Operations and bytes that a training step of the routed-experts LM
(``lfm2_moe``: gated short convolutions, grouped-query attention, a leading
dense SwiGLU, then routed experts of which this chip holds a share) and its
grouped matrix products NEED, from shapes: what the algorithm has to do, not
what an implementation happens to do (no recomputation, no masked-out work,
no row past a group). The configuration names this module under
``model.costs``; the ``lmpretrain`` drivers call ``step_flops``, ``kernels``
and ``reader_values`` with the configuration as run.

``step_flops`` counts the experts AT THE UNIFORM SHARE (tokens x
experts_per_token x held / total pairs an expert layer): a number from
shapes, as ``HybridLM.fit_facts`` counts them, so ``estimator.mfu`` does not
move with the routing. The grouped products' roofline counts the pairs that
WERE routed here (``reader_values`` reads the program's own count): the same
needed work whatever implements the product."""

from __future__ import annotations

import re

from . import lm_costs, xplane


def _dims(config: dict) -> dict:
    share = config.get("share", {})
    first, depth = share.get("first_layer", 0), config["num_hidden_layers"]
    kinds = list(config["layer_types"][first:first + depth])
    dense = config["num_dense_layers"]
    held = config["num_experts"]
    return {
        "hidden": config["hidden_size"], "vocab": config["vocab_size"],
        "dense_ffn": config["intermediate_size"],
        "expert_ffn": config["moe_intermediate_size"],
        "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "taps": config["conv_L_cache"],
        "conv": kinds.count("conv"), "attention": kinds.count("full_attention"),
        "dense_layers": dense, "expert_layers": depth - dense,
        "held": held, "total": share.get("experts_total", held),
        "per_token": config["num_experts_per_tok"],
    }


def uniform_pairs(config: dict, tokens: int) -> int:
    """Pairs an expert layer routes here when the load is even."""
    d = _dims(config)
    return tokens * d["per_token"] * d["held"] // d["total"]


def gmm_pair(hidden: int, width: int, itemsize: int) -> dict:
    """One (token, expert) pair through an expert, forward and backward:
    ``fwd`` the two products (2 D 2F + 2 F D), reading the row and the
    activation and writing the first product and the result once; ``bwd``
    two products for each of the forward's (the rows' gradient and the
    weights'), reading both cotangents and both saved operands and writing
    both rows' gradients once."""
    return {
        "fwd": {"flops": 6 * hidden * width,
                "bytes": (2 * hidden + 3 * width) * itemsize},
        "bwd": {"flops": 12 * hidden * width,
                "bytes": (3 * hidden + 4 * width) * itemsize},
    }


def gmm_weights(hidden: int, width: int, held: int, itemsize: int) -> dict:
    """Bytes of the held experts' weights a step and expert layer: read once
    a forward, read once and their gradient written once a backward."""
    return {"bytes": 3 * held * 3 * hidden * width * itemsize}


def step_flops(config: dict, batch: int, t: int) -> dict:
    """Model FLOPs of one training step, forward + backward = 3 x forward:
    ``layers`` (6 x matrix parameters x tokens: mixers, the dense SwiGLU,
    the routers; a convolution's 2 k a channel beside them), ``experts`` (6
    x an expert's parameters x the UNIFORM share of the pairs), ``attention``
    (causal), ``head`` (the tied embedding, once); recomputation does not
    count."""
    d = _dims(config)
    h = d["hidden"]
    conv_mixer = h * 3 * h + h * h + d["taps"] * h
    attention = 2 * h * h + 2 * h * d["kv_heads"] * d["head_dim"]
    ffn = (d["dense_layers"] * 3 * h * d["dense_ffn"]
           + d["expert_layers"] * h * d["total"])
    tokens = batch * t
    parts = {
        "layers": 6 * (d["conv"] * conv_mixer + d["attention"] * attention
                       + ffn) * tokens,
        "experts": d["expert_layers"] * 6 * 3 * h * d["expert_ffn"]
        * batch * uniform_pairs(config, t),
        "attention": 3 * d["attention"] * 4 * h * (t * (t + 1) // 2) * batch,
        "head": 6 * h * d["vocab"] * tokens,
    }
    parts["total"] = sum(parts.values())
    return parts


def kernels(config: dict, batch: int, t: int, itemsize: int = 2) -> dict:
    """Needed work, for the roofline readers: the flash kernels a call (over
    the query heads: K and V are repeated to them in HBM), and the grouped
    products PER PAIR forward and backward with the weights' bytes a step
    and layer (``layers``: how many expert layers a step)."""
    d = _dims(config)
    pair = gmm_pair(d["hidden"], d["expert_ffn"], itemsize)
    return {
        "flash_fwd": {"cost": lm_costs.flash_fwd(
            batch, d["q_heads"], t, d["head_dim"], itemsize)},
        "flash_bwd": {"cost": lm_costs.flash_bwd(
            batch, d["q_heads"], t, d["head_dim"], itemsize)},
        "moe_gmm": {
            "per_pair": {k: pair["fwd"][k] + pair["bwd"][k]
                         for k in ("flops", "bytes")},
            "weights_per_layer_step": gmm_weights(
                d["hidden"], d["expert_ffn"], d["held"], itemsize),
            "layers": d["expert_layers"]},
    }


def _program_value(name: str):
    """A gauge or counter of the program's, as it stands; None where the
    program has none (a program from before the expert layer)."""
    try:
        from raydp_tpu import obs

        return obs.metrics.snapshot().get(name, {}).get("value")
    except Exception:  # noqa: BLE001 - nothing to read is an answer
        return None


# the program's cumulative counters of the training steps' report, as they
# stood at each fence the driver noted (the traced stretch's first and last)
STRETCH_COUNTERS = ("model.experts.pairs_held", "model.experts.steps_reported")
_fences: list = []


def note_fence() -> None:
    _fences.append({name: _program_value(name) for name in STRETCH_COUNTERS})


def _over_the_stretch(name: str):
    """A counter's growth between the first and the last fence noted; None
    without two fences or from a program without the counter."""
    if len(_fences) < 2 or None in (_fences[0][name], _fences[-1][name]):
        return None
    return _fences[-1][name] - _fences[0][name]


def reader_values(config: dict, batch: int, t: int) -> dict:
    """What the expert layer's readers need: the axes by which they tell its
    operations from the rest of the step (``rows``: the buffer's bound as
    the program cuts it for the batch's tokens, ``ops.experts.row_bound_for``;
    None from a program without the layer), and the program's own word on
    the load, from the report the training steps summed inside the epoch
    program: the pairs computed here and the steps reported IN THE TRACED
    STRETCH (``note_fence``), and the gauge of the newest epoch fenced."""
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
        "moe_pairs_in_trace": _over_the_stretch("model.experts.pairs_held"),
        "moe_steps_reported_in_trace": _over_the_stretch(
            "model.experts.steps_reported"),
        "moe_load_max_over_mean": _program_value(
            "model.experts.load_max_over_mean"),
        "moe_pairs_dropped": _program_value("model.experts.pairs_dropped"),
    }


# -- the expert layer's operations in a device trace ---------------------------

GMM = re.compile(r"^%[\w.\-]*(ragged-dot|gmm)")


def _arrays(text: str):
    """[(dtype, dims)] of every array type in a piece of an HLO line."""
    return [(dtype, tuple(int(x) for x in dims.split(",") if x))
            for dtype, dims in re.findall(r"\b(\w+)\[([\d,]*)\]", text)]


def _is_expert_array(dtype: str, dims: tuple, axes: dict, result: bool) -> bool:
    n, k = axes["tokens"], axes["per_token"]
    rows, d, f = axes["rows"], axes["hidden"], axes["width"]
    size = 1
    for x in dims:
        size *= x
    return bool(
        (rows and dims and dims[0] == rows and dims[-1] in (d, f, 2 * f))
        or (rows and dims == (rows,))  # a row's token, pair, validity
        or (size == n * k and len(dims) <= 2)  # a number a pair
        or dims == (n, axes["total"])  # the router's scores
        # the held experts' weights in the operands' dtype: the cast
        or (result and dtype == "bf16" and dims in (
            (axes["held"], d, 2 * f), (axes["held"], f, d))))


def moe_seconds(ops: dict, axes: dict) -> float:
    """Summed device seconds of the expert layers' operations, route to
    combine, among a trace's ``ops`` ({HLO line: (calls, seconds)}).

    A trace's events carry the HLO line (result and operand types) and no
    ``op_name``, so the scope ``hybridlm.experts`` cannot be read there: an
    operation counts as the expert layer's when it is the grouped product
    (``GMM``: ``%ragged-dot-*`` with its metadata call, or ``%*gmm*``) or an
    array of its RESULT OR OPERANDS has the layer's own layout: the rows'
    buffer ([rows, hidden | width | 2 width], or a [rows] vector); a number
    a (token, choice) pair ([tokens x k] or [tokens, k]: the sort, the
    selection, the weights, the ranks); the router's scores [tokens,
    total]; or, as a result, the held experts' stacked weights in bf16 (the
    cast a step pays for float32 parameters). That takes the router's
    product and top-k, both sorts, the gathers of dispatch and combine in
    both directions (their operands carry the rows), the activation
    between the products and the kernels themselves, forward, recomputed
    and backward. It misses the float32 norm before the router (a [tokens,
    hidden] fusion like any layer's) and the optimizer's update of the
    experts' weights (float32 [held, ...]: the optimizer's, not the
    layer's); it would take any other operation whose shapes happened to
    carry those axes, and this cell's other layers have none."""
    if not axes or not axes.get("rows"):
        return 0.0
    total = 0.0
    for name, (_, seconds) in ops.items():
        head, _, rest = name.partition(" = ")
        result = xplane.result_type(name)
        operands = rest[len(result):] if rest.startswith(result) else rest
        if GMM.search(name) or any(
                _is_expert_array(dt, dims, axes, True)
                for dt, dims in _arrays(result)) or any(
                _is_expert_array(dt, dims, axes, False)
                for dt, dims in _arrays(operands.split("), ")[0])):
            total += seconds
    return total


def gmm_seconds(ops: dict) -> float:
    """Summed device seconds of the grouped products' kernels (forward,
    recomputed and backward; the metadata call beside them)."""
    return sum(seconds for name, (_, seconds) in ops.items()
               if GMM.search(name))
