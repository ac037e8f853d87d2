"""``estimator.compile_miss_programs``: programs the ``estimator.compile`` spans
got from XLA and not from the persistent cache: the counters
``estimator.compile.programs`` - ``estimator.compile.cache_hits``
(``raydp_tpu/obs/profiler.py``, "compile account"). 0 in a run whose every
program the cache served; the FLOPs probe's and the init program count like
the step programs.

Read from the registry of the driver's own process: the total since the
process started. None where the program has no such counters."""


def read(sources):
    from raydp_tpu import obs

    snap = obs.metrics.snapshot()
    programs = snap.get("estimator.compile.programs")
    hits = snap.get("estimator.compile.cache_hits")
    if programs is None or hits is None:
        return None
    return float(programs["value"]) - float(hits["value"])
