"""``model.ssd_ms``: device milliseconds a training step spends in the
chunked state-space scan's operations, from the decays to ``y`` (forward,
recomputed and backward; not the projections, the convolution or the gated
norm): their summed device time in the traced stretch
(``harness/ssm_costs.ssd_seconds``, which says how it finds them and what it
misses) over the steps the program counted as completed there
(``values["steps_in_trace"]``). The driver gives the scan's axes
(``values["ssd_axes"]``, from the configuration). None without a trace, a
count or a model that has a scan."""

from benchmark.harness import ssm_costs


def read(sources):
    trace = sources.get("trace")
    values = sources.get("values", {})
    steps, axes = values.get("steps_in_trace"), values.get("ssd_axes")
    if trace is None or not steps or not axes:
        return None
    seconds = ssm_costs.ssd_seconds(trace.ops, axes)
    return 1e3 * seconds / steps if seconds > 0 else None
