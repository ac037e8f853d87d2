"""``estimator.compile_rest_s``: what of the ``estimator.compile`` spans jax
reported no stage of: the span's wall less trace, lower, backend and cache
load (at the ``init`` site the init program's RUN, at ``flops_probe`` the cost
analysis). The counter ``estimator.compile.rest_seconds``
(``raydp_tpu/obs/profiler.py``, "compile account").

Read from the registry of the driver's own process: the total since the
process started, the warm-up fit and the window's fit together. None where the
program has no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("estimator.compile.rest_seconds")
    return None if counter is None else float(counter["value"])
