"""``estimator.compile_cache_load_s``: seconds of the ``estimator.compile`` spans
that were the persistent cache's LOAD of a program
(``/jax/core/compile/backend_compile_duration`` with
``/jax/compilation_cache/cache_hits`` inside the interval). The counter
``estimator.compile.cache_load_seconds`` (``raydp_tpu/obs/profiler.py``,
"compile account").

Read from the registry of the driver's own process: the total since the
process started, the warm-up fit and the window's fit together. None where the
program has no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("estimator.compile.cache_load_seconds")
    return None if counter is None else float(counter["value"])
