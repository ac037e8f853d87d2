"""``model.moe_ms``: device milliseconds a training step spends in the expert
layers' operations, route to combine (the router's product and top-k, the
sort, the gathers of dispatch and combine, the grouped products and the
activation between them; forward, recomputed and backward): their summed
device time in the traced stretch (``harness/moe_costs.moe_seconds``, which
says how it finds them and what it misses) over the steps the program counted
as completed there (``values["steps_in_trace"]``). The driver gives the
layer's axes (``values["moe_axes"]``, from the configuration and the
program). None without a trace, a count or a model with an expert layer."""

from benchmark.harness import moe_costs


def read(sources):
    trace = sources.get("trace")
    values = sources.get("values", {})
    steps, axes = values.get("steps_in_trace"), values.get("moe_axes")
    if trace is None or not steps or not axes:
        return None
    seconds = moe_costs.moe_seconds(trace.ops, axes)
    return 1e3 * seconds / steps if seconds > 0 else None
