"""``exchange.stage_s``: seconds the program spent staging the ETL frame to
host arrays and to the device before a fit's first dispatch — the counter
``exchange.stage_seconds``, which the ``exchange.stage`` spans feed
(``_stage_host``, the resident runner's device staging, the streamed fit's
sample block and its producer's start to the first segment handed over).

Read from the registry of the driver's own process, so it is the total since
the process started: the warm-up fit and the window's fit together (same
runner, same shapes), all of it inside ``setup_s``. None where the program has
no such counter."""


def read(sources):
    from raydp_tpu import obs

    counter = obs.metrics.snapshot().get("exchange.stage_seconds")
    return None if counter is None else float(counter["value"])
