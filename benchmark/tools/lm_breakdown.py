#!/usr/bin/env python3
"""Where a looped LM's traced steps spend the device's time, by kind of
operation (PERF.md, section 5):

    python3 benchmark/tools/lm_breakdown.py <file.xplane.pb | trace dir> <steps traced> <vocabulary size>

Classes, each an operation's whole HLO line matched in this order: the flash
forward kernel (``flash_attention_fwd``: the forward pass's calls, the
backward pass's recomputation and the evaluation's), the flash backward
kernels, the optimizer (a result that is a tuple of two or more equal float32
shapes: parameter and moments), the exit loss (the vocabulary axis in the
result or in an operand: logits, their recomputation, softmax statistics, the
logits' gradient, the head's weight gradient, the product back to the hidden
state), everything else (the layers' matmuls with their norms, RoPE and
elementwise work). Milliseconds are per step traced; the evaluations inside
the trace are in them."""

from __future__ import annotations

import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import xplane  # noqa: E402


def classify(op: str, vocab: int) -> str:
    name = op.partition(" = ")[0]
    if "flash_attention_fwd" in name:
        return "flash forward kernel"
    if "flash_attention_bwd" in name:
        return "flash backward kernels"
    result = xplane.result_type(op)
    shapes = re.findall(r"f32\[[\d,]+\]", result)
    if result.startswith("(") and len(shapes) >= 2 and len(set(shapes)) == 1 \
            and len(shapes) == result.count("["):
        return "optimizer update"
    if re.search(rf"[\[,]{vocab}[\],]", op):
        return "exit loss (vocabulary axis)"
    return "layers and the rest"


def main(argv) -> int:
    path, steps, vocab = argv[1], int(argv[2]), int(argv[3])
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    summary = xplane.reduce_trace(path)
    classes: dict = {}
    for op, (calls, seconds) in summary.ops.items():
        slot = classes.setdefault(classify(op, vocab), [0, 0.0])
        slot[0] += calls
        slot[1] += seconds
    total = sum(s for _, s in classes.values())
    print(f"window {summary.window_s:.3f} s, busy {summary.busy_s:.3f} s, "
          f"{steps} steps: {1e3 * summary.busy_s / steps:.1f} ms busy a step")
    for kind, (calls, seconds) in sorted(classes.items(), key=lambda kv: -kv[1][1]):
        print(f"{1e3 * seconds / steps:9.1f} ms a step  {100 * seconds / total:5.1f} %  "
              f"{calls / steps:8.1f} calls a step  {kind}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
