"""``estimator.compile_trace_s``: seconds of the ``estimator.compile`` spans that
were Python TRACING of a jitted function
(``/jax/core/compile/jaxpr_trace_duration``, the union of the nested intervals
on the site's thread). The counter ``estimator.compile.trace_seconds``
(``raydp_tpu/obs/profiler.py``, "compile account").

Read from the registry of the driver's own process: the total since the
process started, the warm-up fit and the window's fit together. None where the
program has no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("estimator.compile.trace_seconds")
    return None if counter is None else float(counter["value"])
