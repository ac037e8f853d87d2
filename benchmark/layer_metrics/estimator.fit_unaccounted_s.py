"""``estimator.fit_unaccounted_s``: what of a fit's start (fit start to its first
epoch record) no child span of ``estimator.fit`` held: mesh and model
resolution, ``device_put`` of the parameters, the optimizer's re-init. The
counter ``estimator.fit.unaccounted_seconds`` (``raydp_tpu/obs/profiler.py``,
"compile account").

Read from the registry of the driver's own process: the total since the
process started, the warm-up fit and the window's fit together. None where the
program has no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("estimator.fit.unaccounted_seconds")
    return None if counter is None else float(counter["value"])
