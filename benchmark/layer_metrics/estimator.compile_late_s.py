"""``estimator.compile_late_s``: compile seconds anywhere in the process AFTER a
fit's first fence and before its last: 0 where the shapes are steady; paid in
``fit_samples_per_s``, not in set-up. The counter
``estimator.compile.late_seconds`` (``raydp_tpu/obs/profiler.py``, "compile
account").

Read from the registry of the driver's own process: the total since the
process started, the warm-up fit and the window's fit together. None where the
program has no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("estimator.compile.late_seconds")
    return None if counter is None else float(counter["value"])
