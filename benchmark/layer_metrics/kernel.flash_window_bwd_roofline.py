"""``kernel.flash_window_bwd_roofline``: share of its roofline of the flash
attention backward pass OF A WINDOW LAYER, which the program runs as two
Mosaic calls (``flash_attention_window_bwd_dq`` and
``flash_attention_window_bwd_dkv``, names of their own: ``kernel.
flash_bwd_roofline`` counts every pass as causal and reads the global
layers' alone). One backward pass is one dq call and one dk/dv call: passes
x the least time one pass needs (``harness/window_costs.flash_window_bwd``
against the peaks table: four products a pair the window keeps, no
recomputed scores) over the summed device time of both calls. None where
the trace holds no such call (a program without window layers, or no
trace) or the configuration's costs give no window call."""

import re

from benchmark.harness import costs

DQ = re.compile(r"^%[\w.\-]*flash_attention_window_bwd_dq")
DKV = re.compile(r"^%[\w.\-]*flash_attention_window_bwd_dkv")


def read(sources):
    trace = sources.get("trace")
    kernel = sources.get("kernels", {}).get("flash_window_bwd")
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
