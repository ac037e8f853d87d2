"""``model.delta_scope_ms``: device milliseconds a training step spends under
the device scope ``delta_rule`` (``raydp_tpu/ops/delta_rule.py``: the gated
delta rule from the running log-decay sums to ``o``: the chunk's score
matrices, its triangular solve, the serial recurrence over the chunk states
and the read-out; forward, recomputed and backward; not the projections, the
convolution, the l2 norms or the gated read-out norm, which lie under
``hybridlm.delta`` around it), the traced stretch's evaluations included,
over the steps the program counted as completed there
(``values["steps_in_trace"]``). Membership as the PROGRAM gives it
(``harness/scopes.py``: the trace's operations joined to
``obs.profiler.device_scopes()`` by instruction name), so a scan written as
one kernel is found as the scan in XLA's operations is. Not in it: copies the
compiler makes for the scan that carry no ``op_name``
(``device.scope_unattributed_share`` holds them). None without a trace, a
count, a program that gives the map, or a program that has no such scope."""

from benchmark.harness import scopes


def read(sources):
    if not sources.get("values", {}).get("steps_in_trace"):
        return None
    return scopes.member_ms_per_step(sources, "delta_rule")
