"""``model.moe_likely_bound_share``: percent of the newest epoch's expert
layers x TRAINING steps that ran at the likely rows' bound
(``ops.experts.SLACK`` x the even share) and not in the worst-case arm of
the layer's conditional: the program's gauge
``model.experts.likely_bound_share`` x 100 (the training steps' report,
summed inside the epoch program and fetched at the epoch's fence; 1 - overflows
/ (steps x expert layers)). 100 where the balancing rule holds the load; a
fall costs a step the worst-case passes and explains a fall of
``fit_samples_per_s`` that no device metric of a traced stretch does. None
where the program has no such gauge."""


def read(sources):
    from raydp_tpu import obs

    gauge = obs.metrics.snapshot().get("model.experts.likely_bound_share")
    return None if gauge is None else 100.0 * float(gauge["value"])
