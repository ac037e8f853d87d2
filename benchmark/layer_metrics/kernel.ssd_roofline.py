"""``kernel.ssd_roofline``: share of its roofline of the chunked state-space
scan: the least time the chip could take for the scans of the steps traced
(a forward and a backward scan a Mamba layer and step:
``harness/ssm_costs.ssd_fwd`` / ``ssd_bwd`` against the peaks table; the
recomputed forward is not needed work) over the summed device time of the
scan's operations (``harness/ssm_costs.ssd_seconds``: the time
``model.ssd_ms`` reports). None without a trace, a count of steps or a model
that has a scan."""

from benchmark.harness import costs, ssm_costs


def read(sources):
    trace = sources.get("trace")
    values, kernels = sources.get("values", {}), sources.get("kernels", {})
    steps, axes = values.get("steps_in_trace"), values.get("ssd_axes")
    fwd, bwd = kernels.get("ssd_fwd"), kernels.get("ssd_bwd")
    if trace is None or not steps or not axes or not fwd or not bwd:
        return None
    seconds = ssm_costs.ssd_seconds(trace.ops, axes)
    if seconds <= 0:
        return None
    least = sum(costs.roofline(k["cost"], sources["peaks"])["min_s"]
                for k in (fwd, bwd))
    return 100.0 * steps * fwd["layers"] * least / seconds
