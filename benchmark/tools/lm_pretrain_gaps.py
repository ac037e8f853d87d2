#!/usr/bin/env python3
"""The readings behind an ``lmpretrain`` cell's limits, over many seeds in
ONE process (the reference and the program's objectives compile once, not
once a seed as in a run):

    python3 benchmark/tools/lm_pretrain_gaps.py <workload> <first seed> <seeds> [--low N] [--budget-s S] [--rehearse-on-cpu]

(``--budget-s``: no seed is STARTED after so many seconds.) For each seed,
what ``drivers/lmpretrain.py`` part (a) compares: the objective as run and
matched against the reference (loss, logits, gradients), the estimator's
epoch program against the two replayed epochs (``step_own``, ``step``), and
for the first ``N`` seeds the second reading (the reference itself in bf16:
its objective, and its own epoch). One JSON line a seed, also in
``chiprun_out/lm_pretrain_gaps.jsonl``. Decides nothing."""

from __future__ import annotations

import argparse
import importlib
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
    ctx = Ctx(ROOT, cell, "gaps", args.first_seed, 0.0, False,
              args.rehearse_on_cpu, tempfile.mkdtemp(prefix="raydp-gaps-"))
    t_start = time.time()
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    code = 1
    try:
        ctx.claim_device()
        c, tr = ctx.config, ctx.traffic
        model_class = lm._named(c["model"]["class"])
        module = model_class.from_config(c, **c["model"]["kwargs"])
        ref = importlib.import_module(c["model"]["reference"])
        session = lm.lmfit.start_etl(ctx)
        with open(os.path.join(out_dir, "lm_pretrain_gaps.jsonl"), "a") as log:
            for i in range(args.seeds):
                if time.time() - t_start > args.budget_s:
                    ctx.say(f"budget of {args.budget_s} s spent: {i} seeds done")
                    break
                seed, t0 = args.first_seed + i, time.time()
                table, raw = tokens.raw_frame(
                    seed, int(tr["rows"]), int(tr["seq_len"]),
                    int(c["vocab_size"]), float(tr["zipf_a"]),
                    float(tr["bigram_tilt"]))
                train, held, _ = lm.lmfit.preprocess(ctx, session, table, seed)
                part_b, rows = lm.lmfit.check_data(ctx, raw, train, held)
                a = lm.check_objective(
                    ctx, module, ref,
                    lm.lmfit.make_estimator(ctx, module, seed, 1),
                    rows["train"], rows["held_out"], seed,
                    lower_reading=i < args.low)
                step = lm.lmfit.check_step(ctx, module, train, held, seed, a)
                line = {"seed": seed, "b": part_b["ok"],
                        "seconds": round(time.time() - t0, 1)}
                line.update({k: v for k, v in {**a, **step}.items()
                             if isinstance(v, (bool, int, float))})
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
                log.flush()
                del a
        code = 0
    finally:
        try:
            lm.lmfit.stop_etl()
        finally:
            os._exit(code)


if __name__ == "__main__":
    sys.exit(main())
