"""``estimator.mfu_program``: the program's own live MFU, in percent — gauge
``estimator.mfu`` x 100 as the last finished epoch left it: XLA's FLOPs count
of one step x steps the device COMPLETED over the wall time between two
closing fences (evaluation and restart inside), over the peak of the device
kind. ``estimator.mfu`` of this benchmark is the same ratio from the host
clock and a FLOPs count from shapes.

The gauge is read only where the program counts completed steps
(``estimator.steps_completed``): before it did, the gauge of that name was
divided by the host's time in dispatches and meant something else. None
there."""


def read(sources):
    from raydp_tpu import obs

    snap = obs.metrics.snapshot()
    gauge = snap.get("estimator.mfu")
    if gauge is None or "estimator.steps_completed" not in snap:
        return None
    return 100.0 * float(gauge["value"])
