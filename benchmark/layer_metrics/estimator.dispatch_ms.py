"""``estimator.dispatch_ms``: the host's milliseconds inside one compiled
call of the fit (a 32-step segment scan when streamed, a whole epoch's scan
when resident), mean per DISPATCH — the step profiler's ``dispatch`` phase,
histogram ``estimator.step.dispatch_ms``, not divided by the segment's steps.
The call returns before the device finishes: this is what the host pays to
hand the device its work, never device time.

Read from the registry of the driver's own process: every dispatch since the
process started (compiling first calls are kept out by the program). None
where the program has no such histogram or it saw no dispatch."""


def read(sources):
    from raydp_tpu import obs

    hist = obs.metrics.snapshot().get("estimator.step.dispatch_ms")
    if not hist or not hist.get("count"):
        return None
    return float(hist["sum"]) / hist["count"]
