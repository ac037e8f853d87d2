"""``estimator.tok_s_program``: the program's own tokens per second — gauge
``estimator.tokens_per_sec`` as the last finished epoch left it: tokens of
the steps the device COMPLETED over the wall time between two closing fences
(evaluation and restart inside). Read from the registry of the driver's own
process. None where the program has no such gauge."""


def read(sources):
    from raydp_tpu import obs

    gauge = obs.metrics.snapshot().get("estimator.tokens_per_sec")
    return None if gauge is None else float(gauge["value"])
