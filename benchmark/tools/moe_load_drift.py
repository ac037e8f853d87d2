#!/usr/bin/env python3
"""How the held experts' load and a step's time move over the first steps of
a fit of a routed cell, seed by seed (the cell's model, batch and AdamW; the
step is ``make_train_step``'s, one dispatch a step, each fenced):

    chiprun -- python3 benchmark/tools/moe_load_drift.py <workload> <steps> <seed>[,<seed>...] [<optimizer> ...]

``<optimizer>`` lays ``key=value,...`` over the configuration's
``model.adamw`` (``warmup_steps=0,expert_bias_rate=0`` is the optimizer
without the warm-up and the balancing rule); each one given is run on every
seed, none given runs the configuration's own. Prints, per seed and step:
the loss, the pairs the held experts were given in each expert layer (the
uniform share is tokens x k x held / total a layer) and the step's wall
time; per seed the mean pairs and step time from step 3 on (a window opens
after a fit's first epoch). Decides nothing."""

from __future__ import annotations

import importlib
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import cells, tokens
    from raydp_tpu import models
    from raydp_tpu.estimator.jax_estimator import MODEL_LOSS, make_train_step

    cell = cells.resolve(ROOT, sys.argv[1])
    steps, seeds = int(sys.argv[2]), [int(s) for s in sys.argv[3].split(",")]
    variants = [{k: float(v) for k, v in (kv.split("=") for kv in arg.split(",") if kv)}
                for arg in sys.argv[4:]] or [{}]
    rehearsal = jax.devices()[0].platform != "tpu"
    c, tr = cells.sized(cell.config, rehearsal), cells.sized(cell.traffic, rehearsal)
    module_path, _, name = c["model"]["class"].rpartition(".")
    module = getattr(importlib.import_module(module_path), name).from_config(
        c, **c["model"]["kwargs"])
    batch, rows = int(tr["batch"]), int(tr["train_rows"])
    for variant, seed in ((v, s) for v in variants for s in seeds):
        hyper = {**c["model"]["adamw"], **variant}
        print(f"optimizer {hyper}", flush=True)
        tx = getattr(models, c["model"]["optimizer"])(**hyper)
        step = jax.jit(make_train_step(module, MODEL_LOSS, tx).reporting,
                       donate_argnums=(0, 1))
        ids = tokens.sequences(seed, int(tr["rows"]), int(tr["seq_len"]),
                               int(c["vocab_size"]), float(tr["zipf_a"]),
                               float(tr["bigram_tilt"]))[:rows]
        x = jnp.asarray(ids)
        params = jax.jit(lambda r: module.init(r, x[:batch], None, method="loss"))(
            jax.random.PRNGKey(seed % (2 ** 31)))
        state = tx.init(params)
        rng = np.random.default_rng(seed)
        seen = []
        for i in range(steps):
            if i % (rows // batch) == 0:
                order = rng.permutation(rows)
            pick = order[(i % (rows // batch)) * batch:][:batch]
            t0 = time.perf_counter()
            params, state, loss, report = step(
                params, state, jnp.zeros((), jnp.float32), x[pick], None)
            load = np.asarray(report["expert_load"])
            ms = (time.perf_counter() - t0) * 1e3
            seen.append((load.sum(), ms))
            print(f"seed {seed} step {i}: loss {float(loss):.3f} held pairs a "
                  f"layer {load.sum(axis=1).astype(int).tolist()} (largest "
                  f"expert {int(load.max())}) step {ms:.0f} ms", flush=True)
        after = np.array(seen[3:])
        if len(after):
            print(f"seed {seed} {variant or 'as configured'}: from step 3 on "
                  f"{after[:, 0].mean():.0f} pairs a step, "
                  f"{after[:, 1].mean():.1f} ms a step", flush=True)
        del params, state
    return 0


if __name__ == "__main__":
    sys.exit(main())
