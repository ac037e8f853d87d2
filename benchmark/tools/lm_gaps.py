#!/usr/bin/env python3
"""The readings behind an ``lmfit`` cell's limits, over many seeds in ONE
process (the reference compiles once, not once a seed as in a run):

    python3 benchmark/tools/lm_gaps.py <workload> <first seed> <seeds> [--low N] [--budget-s S] [--rehearse-on-cpu]

(``--budget-s``: no seed is STARTED after so many seconds; the first seed
pays the reference's compile, 467 s with ``--low 1`` on the chip.)

For each seed, what ``drivers/lmfit.py`` part (a) compares: the objective as
run against the reference (loss, logits, exit distribution, gradients), the
estimator's epoch program against the reference's epoch (mean training
loss, each parameter's change and its norm), and for the first ``N`` seeds
the second reading (the reference itself in bf16, its objective and its own
epoch). Beside them, where the epoch's loss gap comes from: each step's loss
by the reference, the program's second-step loss (twice the epoch's mean
less its objective's loss on the first batch), and the program's objective
at the REFERENCE's parameters after the first step on the second batch (the
forward pass's rounding there, without the path's divergence). One JSON
line a seed, also in ``chiprun_out/lm_gaps.jsonl``. Decides nothing."""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("RAYDP_TPU_NO_GLOBAL_ZYGOTE", "1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("first_seed", type=int)
    ap.add_argument("seeds", type=int)
    ap.add_argument("--low", type=int, default=0)
    ap.add_argument("--budget-s", type=float, default=1e9)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmark.harness import cells, tokens
    from benchmark.harness.child import Ctx

    cell = cells.resolve(ROOT, args.workload)
    lm = cells.load_module(cell.driver_path, f"traffic kind {cell.kind!r}")
    lm.MODES = ("as_run",)
    ctx = Ctx(ROOT, cell, "gaps", args.first_seed, 0.0, False,
              args.rehearse_on_cpu, tempfile.mkdtemp(prefix="raydp-gaps-"))
    t_start = time.time()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    code = 1
    try:
        ctx.claim_device()
        import jax
        import jax.numpy as jnp

        from benchmark.reference import ouro

        session = lm.start_etl(ctx)
        module = lm.make_model(ctx)
        c, tr = ctx.config, ctx.traffic
        batch = int(tr["batch"])
        first_step = []
        plain_step = ouro.adamw_step

        def spy(leaves, *rest, **kw):
            out = plain_step(leaves, *rest, **kw)
            if not first_step:
                first_step.append(out[0])
            return out

        ouro.adamw_step = spy
        took = 0.0
        with open(os.path.join(out_dir, "lm_gaps.jsonl"), "a") as log:
            for i in range(args.seeds):
                if time.time() - t_start > args.budget_s:
                    ctx.say(f"budget: no seed is started after {args.budget_s:.0f} s"
                            f" (the last took {took:.0f} s; the first pays the"
                            " reference's compile)")
                    break
                t0 = time.time()
                seed = args.first_seed + i
                first_step.clear()
                table, raw = tokens.raw_frame(
                    seed, int(tr["rows"]), int(tr["seq_len"]), c["vocab_size"],
                    float(tr["zipf_a"]), float(tr["bigram_tilt"]))
                train, held, _ = lm.preprocess(ctx, session, table, seed)
                b, rows = lm.check_data(ctx, raw, train, held)
                a = lm.check_objective(
                    ctx, module, lm.make_estimator(ctx, module, seed, 1),
                    rows["train"], rows["held_out"], seed,
                    lower_reading=i < args.low)
                order = a["order"]
                run_as = lm._objective(module)
                theta0 = jax.tree.unflatten(
                    a["treedef"], [jnp.asarray(v) for v in a["theta0"]])
                (own1, _), _ = run_as(theta0, jnp.asarray(
                    rows["train"][order[:batch]]))
                del theta0
                theta1 = jax.tree.unflatten(
                    a["treedef"], [jnp.asarray(v) for v in first_step[0]])
                (fwd2, _), _ = run_as(theta1, jnp.asarray(
                    rows["train"][order[batch:2 * batch]]))
                own1, fwd2 = float(own1), float(fwd2)
                del theta1
                first_step.clear()
                step = lm.check_step(ctx, module, train, held, seed, a)
                mean_ref = a["ref_epoch_loss"]
                line = {k: v for k, v in {**a, **step}.items()
                        if isinstance(v, (int, float, bool, str))
                        or k == "ref_step_losses"}
                line.update(
                    seed=seed, data_ok=b["ok"],
                    program_step1_loss=own1,
                    program_step2_loss=2 * step["step.loss"] - own1,
                    own_step_losses=a["own_step_losses"],
                    program_at_reference_theta1_loss=fwd2,
                    forward_gap_step2=fwd2 - a["ref_step_losses"][1])
                took = time.time() - t0
                line["seconds"] = round(took, 1)
                text = json.dumps(line)
                print("GAPS " + text, flush=True)
                log.write(text + "\n")
                log.flush()
        code = 0
    except BaseException:  # noqa: BLE001 - reported, then we leave
        import traceback

        traceback.print_exc()
    try:
        lm.stop_etl()
    finally:
        os._exit(code)


if __name__ == "__main__":
    main()
