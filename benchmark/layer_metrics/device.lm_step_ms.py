"""``device.lm_step_ms``: device-busy milliseconds per training step: the
union of the device's operation intervals in the traced stretch (the held-out
evaluations inside it included) over the steps the PROGRAM counted as
completed there (``values["steps_in_trace"]``: ``estimator.steps_completed``
between the two fences that bracket the trace). ``device.step_ms`` divides by
the call count most operations share, which a body that runs 4 or 24 times a
step defeats. None without a trace or a count."""


def read(sources):
    trace = sources.get("trace")
    steps = sources.get("values", {}).get("steps_in_trace")
    if trace is None or not steps or trace.busy_s <= 0:
        return None
    return 1e3 * trace.busy_s / steps
