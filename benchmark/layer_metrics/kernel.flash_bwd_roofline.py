"""``kernel.flash_bwd_roofline``: share of its roofline of the flash
attention backward pass, which this program runs as two Mosaic calls
(``flash_attention_bwd_dq`` and ``flash_attention_bwd_dkv``, the names the
program gives them; an instruction's name carries the transformations it
was traced under too: ``%transpose_jvp_flash_attention_bwd_dq__.1``). One
backward pass is one dq call and one dk/dv call: passes x the least time one
pass needs (``harness/lm_costs.flash_bwd``
against the peaks table: causal, four products a kept pair, no recomputed
scores) over the summed device time of both calls. None where the trace
holds no such call (a program that does not name them, or no trace)."""

import re

from benchmark.harness import costs

DQ = re.compile(r"^%[\w.\-]*flash_attention_bwd_dq")
DKV = re.compile(r"^%[\w.\-]*flash_attention_bwd_dkv")


def read(sources):
    trace = sources.get("trace")
    kernel = sources.get("kernels", {}).get("flash_bwd")
    if trace is None or kernel is None:
        return None
    passes, seconds = 0, 0.0
    for name, (calls, total) in trace.ops.items():
        if DQ.search(name):
            passes += calls
            seconds += total
        elif DKV.search(name):
            seconds += total
    if not passes or seconds <= 0:
        return None
    least = costs.roofline(kernel["cost"], sources["peaks"])["min_s"]
    return 100.0 * passes * least / seconds
