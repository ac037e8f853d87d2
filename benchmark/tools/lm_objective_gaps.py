#!/usr/bin/env python3
"""The OBJECTIVE's readings behind an ``lmpretrain_routed`` cell's ``as_run``
and ``matched`` limits, with the second reading (the control that must come
out NOT correct), over many seeds in ONE process:

    python3 benchmark/tools/lm_objective_gaps.py <workload> <first seed> <seeds> [--budget-s S] [--rehearse-on-cpu]

(``--budget-s``: no seed is STARTED after so many seconds.) For each seed,
the first half of ``drivers/lmpretrain_routed.check_objective`` through the
driver's own functions and the traffic file's own limits: the objective as
run and matched against the reference under the program's routing, and the
reference ITSELF in bf16 from end to end under the as-run routing, with
the ``as_run`` limits that refuse it (``bf16_reference.refused_by``: it must
name one on every seed). No epoch is replayed, which is what
``lm_pretrain_gaps.py --low`` adds and what a cell of 715 M parameters cannot
hold on the chip machine's host (three more copies of the parameters and
AdamW's moments: over 40 GiB, PERF.md section 7). On the chip the first
seed takes 400 s (three compiles of the reference) and each further one 25.
One JSON line a seed, also in ``chiprun_out/lm_objective_gaps.jsonl``.
Decides nothing."""

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
        with open(os.path.join(out_dir, "lm_objective_gaps.jsonl"), "a") as log:
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
                line = {"seed": seed, "b": part_b["ok"]}
                line.update(objective_only(
                    ctx, lm, module, ref,
                    lm.lmfit.make_estimator(ctx, module, seed, 1),
                    rows["train"], seed))
                line["seconds"] = round(time.time() - t0, 1)
                print(json.dumps(line), flush=True)
                log.write(json.dumps(line) + "\n")
                log.flush()
        code = 0
    finally:
        try:
            lm.lmfit.stop_etl()
        finally:
            os._exit(code)


def objective_only(ctx, lm, module, ref, est, train, seed):
    """``check_objective``'s first half alone, its second reading with it."""
    import gc
    import jax
    import jax.numpy as jnp
    import numpy as np

    tr = ctx.traffic
    batch = int(tr["batch"])
    order = np.asarray(est.epoch_order(0, len(train)))
    x = jnp.asarray(train[order[:batch]])
    cfg = ref.config_of(ctx.config)
    block = int(tr["reference_token_block"])
    params = jax.jit(lambda r: module.init(r, x, None, method="loss"))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    _, groups = lm.lmfit.grad_groups(params)
    reference = lm.RoutedReference(ref, cfg, block)
    out = {}
    for mode in lm.MODES:
        if mode == "matched":
            variant = module.clone(dtype=jnp.float32)
            with jax.default_matmul_precision("highest"):
                got = lm._mode_gaps(ctx, variant, ref, cfg, reference,
                                    lm.lmfit._objective(variant), params, x,
                                    groups, block)
        else:
            got = lm._mode_gaps(ctx, module, ref, cfg, reference,
                                lm.lmfit._objective(module), params, x,
                                groups, block)
            ref_as_run, routing = got[5], got[6]
        gaps, chosen, _, finite, dropped = got[:5]
        out.update({f"{mode}.{k}": v for k, v in gaps.items()})
        out.update({f"{mode}.{k}": chosen[k] for k in lm.SELECTION})
        out[f"{mode}.pairs_dropped"] = dropped
        out[f"{mode}.finite"] = bool(finite)
        del got
        gc.collect()
    low = reference(params, x, jnp.bfloat16, routing=routing)
    logits_rel, _ = lm.lmpretrain.logits_gap(
        lambda p, h: ref.logits_of(p, h.astype(jnp.bfloat16), cfg,
                                   jnp.bfloat16),
        lambda p, h: ref.logits_of(p, h, cfg),
        params, low["hidden"], ref_as_run["hidden"], block)
    gaps = {"loss_abs": abs(low["loss"] - ref_as_run["loss"]),
            "logits_rel": logits_rel,
            "grads_rel": float(lm.lmfit.group_ratios(
                low["grads"], ref_as_run["grads"], groups).max())}
    limits = tr["arith_tolerance"]["as_run"]
    out.update({f"bf16_reference.{k}": v for k, v in gaps.items()})
    out["bf16_reference.refused_by"] = [
        k for k in lm.GAPS if gaps[k] > limits[k]]
    del low, ref_as_run, params
    gc.collect()
    return out


if __name__ == "__main__":
    sys.exit(main())
