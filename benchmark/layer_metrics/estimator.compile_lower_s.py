"""``estimator.compile_lower_s``: seconds of the ``estimator.compile`` spans that
were LOWERING to MLIR, every Pallas body among it
(``/jax/core/compile/jaxpr_to_mlir_module_duration``, less the traces that
lowering itself makes). The counter ``estimator.compile.lower_seconds``
(``raydp_tpu/obs/profiler.py``, "compile account").

Read from the registry of the driver's own process: the total since the
process started, the warm-up fit and the window's fit together. None where the
program has no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("estimator.compile.lower_seconds")
    return None if counter is None else float(counter["value"])
