"""``estimator.compile_s``: seconds the program spent in lower / compile /
load-from-cache and in the FLOPs probe — the counter
``estimator.compile_seconds``, incremented as each ``estimator.compile`` span
ends, so it can be read while the window's fit is still running.

Read from the registry of the driver's own process: the total since the
process started, the warm-up fit and the window's fit together, all of it
inside ``setup_s``. None where the program has no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("estimator.compile_seconds")
    return None if counter is None else float(counter["value"])
