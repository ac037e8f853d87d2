"""``estimator.compile_backend_s``: seconds of the ``estimator.compile`` spans in
which XLA compiled a program ANEW
(``/jax/core/compile/backend_compile_duration`` with no cache hit inside the
interval). The counter ``estimator.compile.backend_seconds``
(``raydp_tpu/obs/profiler.py``, "compile account").

Read from the registry of the driver's own process: the total since the
process started, the warm-up fit and the window's fit together. None where the
program has no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("estimator.compile.backend_seconds")
    return None if counter is None else float(counter["value"])
