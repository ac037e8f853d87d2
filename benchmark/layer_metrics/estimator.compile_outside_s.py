"""``estimator.compile_outside_s``: compile seconds (trace, lower, backend) on
threads with NO ``estimator.compile`` span open: the evaluation's programs,
the drivers' eager ``jnp`` operations and conversions, the benchmark's own
checks. The counter ``jax.compile.outside_seconds``
(``raydp_tpu/obs/profiler.py``, "compile account").

Read from the registry of the driver's own process: the total since the
process started, the warm-up fit and the window's fit together. None where the
program has no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("jax.compile.outside_seconds")
    return None if counter is None else float(counter["value"])
