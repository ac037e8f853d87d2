"""``estimator.restart_ms``: milliseconds per epoch boundary from the return
of the epoch's closing fence (the held-out evaluation's loss fetch) to the
return of the next epoch's first dispatch — histogram
``estimator.epoch.restart_ms``, mean. In that time the device provably has
nothing to do because of the host: history append, permutation ship
(resident) or queue get (streamed), dispatch.

Read from the registry of the driver's own process: every epoch boundary
since the process started. None where the program has no such histogram or
it saw no boundary."""


def read(sources):
    from raydp_tpu import obs

    hist = obs.metrics.snapshot().get("estimator.epoch.restart_ms")
    if not hist or not hist.get("count"):
        return None
    return float(hist["sum"]) / hist["count"]
