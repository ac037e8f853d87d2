"""Operations and bytes that a training step of the hybrid state-space /
attention LM and its scan NEED, from shapes: what the algorithm has to do, not
what an implementation happens to do (no recomputation, no masked-out work).
The configuration names this module under ``model.costs``; the
``lmpretrain`` driver calls ``step_flops``, ``kernels`` and ``reader_values``
with the configuration as run.

The scan is counted in its chunked dual form at the published chunk size,
causal pairs inside a chunk: per layer and token pair (i >= j, one chunk) the
scores ``C_i . B_j`` (2 N, shared by the heads: one group) and the product
with ``dt x`` (2 P a head); per token the chunk state it adds to and the state
it reads (2 P N a head each). The per-token recurrence needs more FLOPs (6 P N
a head and token) and the dual form at a smaller chunk fewer; the published
chunk size is the algorithm's own parameter."""

from __future__ import annotations

import re

from . import lm_costs, xplane


def _dims(config: dict) -> dict:
    kinds = list(config["layer_types"][:config["num_hidden_layers"]])
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    return {
        "hidden": config["hidden_size"], "vocab": config["vocab_size"],
        "ffn": config["shared_intermediate_size"],
        "q_heads": config["num_attention_heads"],
        "kv_heads": config["num_key_value_heads"],
        "head_dim": config["hidden_size"] // config["num_attention_heads"],
        "heads": heads, "p": p, "n": config["mamba_d_state"],
        "inner": heads * p, "conv": config["mamba_d_conv"],
        "chunk": config["mamba_chunk_size"],
        "mamba": kinds.count("mamba"), "attention": kinds.count("attention"),
    }


def ssd_pairs(t: int, chunk: int) -> int:
    """Kept (i, j) pairs of a sequence of ``t`` tokens: i >= j, one chunk."""
    q = min(chunk, t)
    return (t // q) * (q * (q + 1) // 2)


def ssd_fwd(batch: int, t: int, heads: int, p: int, n: int, chunk: int,
            itemsize: int) -> dict:
    """One layer's forward scan over [batch, t]: reads x, B, C (operands'
    width) and dt (float32) once, writes y once."""
    pairs = ssd_pairs(t, chunk)
    return {
        "flops": batch * (2 * n * pairs + 2 * heads * p * pairs
                          + 4 * heads * p * n * t),
        "bytes": batch * t * (2 * heads * p * itemsize + 2 * n * itemsize
                              + 4 * heads),
    }


def ssd_bwd(batch: int, t: int, heads: int, p: int, n: int, chunk: int,
            itemsize: int) -> dict:
    """The backward pass of one such call: two products for each of the
    forward's; reads x, B, C, dt and dy once, writes dx, dB, dC and ddt
    once."""
    fwd = ssd_fwd(batch, t, heads, p, n, chunk, itemsize)
    return {
        "flops": 2 * fwd["flops"],
        "bytes": batch * t * (3 * heads * p * itemsize + 4 * n * itemsize
                              + 8 * heads),
    }


def step_flops(config: dict, batch: int, t: int) -> dict:
    """Model FLOPs of one training step, forward + backward = 3 x forward:
    ``layers`` (6 x matrix parameters x tokens, the convolution's taps
    beside them), ``scan``, ``attention`` (causal), ``head`` (the tied
    embedding, once); recomputation does not count."""
    d = _dims(config)
    ffn = 3 * d["hidden"] * d["ffn"]
    mamba = (d["hidden"] * (2 * d["inner"] + 2 * d["n"] + d["heads"])
             + d["inner"] * d["hidden"] + d["conv"] * (d["inner"] + 2 * d["n"])
             + ffn)
    attention = (2 * d["hidden"] * d["hidden"]
                 + 2 * d["hidden"] * d["kv_heads"] * d["head_dim"] + ffn)
    tokens = batch * t
    parts = {
        "layers": 6 * (d["mamba"] * mamba + d["attention"] * attention) * tokens,
        "scan": 3 * d["mamba"] * ssd_fwd(
            batch, t, d["heads"], d["p"], d["n"], d["chunk"], 2)["flops"],
        "attention": 3 * d["attention"] * 4 * d["hidden"]
        * (t * (t + 1) // 2) * batch,
        "head": 6 * d["hidden"] * d["vocab"] * tokens,
    }
    parts["total"] = sum(parts.values())
    return parts


def kernels(config: dict, batch: int, t: int, itemsize: int = 2) -> dict:
    """Needed work a call, for the roofline readers: the flash kernels over
    the query heads (K and V are repeated to them in HBM), and the scan's
    forward and backward of one layer (``layers``: how many a step)."""
    d = _dims(config)
    scan = (batch, t, d["heads"], d["p"], d["n"], d["chunk"], itemsize)
    return {
        "flash_fwd": {"cost": lm_costs.flash_fwd(
            batch, d["q_heads"], t, d["head_dim"], itemsize)},
        "flash_bwd": {"cost": lm_costs.flash_bwd(
            batch, d["q_heads"], t, d["head_dim"], itemsize)},
        "ssd_fwd": {"cost": ssd_fwd(*scan), "layers": d["mamba"]},
        "ssd_bwd": {"cost": ssd_bwd(*scan), "layers": d["mamba"]},
    }


def reader_values(config: dict, batch: int, t: int) -> dict:
    """What the scan's trace readers need of the shapes: the axes by which
    they tell the scan's operations from the rest of the step."""
    d = _dims(config)
    q = min(d["chunk"], t)
    return {"ssd_axes": {"chunks": t // q, "chunk": q, "heads": d["heads"],
                         "head_dim": d["p"], "state": d["n"]}}


# -- the scan's operations in a device trace ----------------------------------


def _is_scan_shape(dims: tuple, axes: dict) -> bool:
    c, q = axes["chunks"], axes["chunk"]
    h, p, n = axes["heads"], axes["head_dim"], axes["state"]
    size = 1
    for d in dims:
        size *= d
    return (
        dims[-2:] == (q, q)  # the decays, the scores, their product
        or (c in dims and q in dims)  # anything laid out chunk by chunk
        or (dims[-2:] == (p, n) and h in dims[:-2])  # the states
        # a number a token and head (dt, log a, their running sums), however
        # the compiler folds the tokens: the cumsum runs over [.., q/128, 128]
        or (h in dims and size == c * q * h)
        # x and y a head, before and after the chunked layout
        or (dims[-2:] == (h, p) and size == c * q * h * p)
    )


def ssd_seconds(ops: dict, axes: dict) -> float:
    """Summed device seconds of the scan's operations among a trace's
    ``ops`` ({HLO line: (calls, seconds)}).

    A trace's events carry the HLO line and no ``op_name``, so the scope
    ``ssd`` cannot be read there: an operation counts as the scan's when an
    array of its RESULT has the scan's own layout: two trailing axes [chunk,
    chunk]; the chunk count and the chunk length as separate axes; a
    trailing [head_dim, state] under the heads; as many numbers as tokens x
    heads with the heads an axis (``dt``, the log-decays, their running
    sums: XLA folds the chunk into [chunk / 128, 128] for the cumsum's
    reduce-window); or [.., heads, head_dim] over all tokens (``x`` and
    ``y`` a head; ``dt``'s softplus outside the scope has the fourth shape
    and is counted too: 2 MB a layer). Checked against the step
    compiled for a described v5e, whose HLO text does carry ``op_name``
    (PR 31): that takes every fusion, product, loop-body operation and
    compiler-inserted copy between the decays and ``y`` (forward, recomputed
    and backward), and misses the scan's last elementwise pass, whose result
    is back in the model's [batch, tokens, heads x head_dim] layout (two
    fusions a layer, fused with the ``D x`` skip), and scalars. It would
    take any other operation of a model whose shapes happened to carry those
    axes; this cell's other layers have none ([tokens, width] matrices,
    [heads, tokens, 64] attention)."""
    total = 0.0
    for name, (_, seconds) in ops.items():
        arrays = re.findall(r"\[([\d,]*)\]", xplane.result_type(name))
        if any(_is_scan_shape(tuple(int(x) for x in a.split(",") if x), axes)
               for a in arrays):
            total += seconds
    return total
