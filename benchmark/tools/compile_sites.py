#!/usr/bin/env python3
"""Where a cell's set-up compiles: the program's compile account
(``raydp_tpu.obs.profiler.compile_account``, docs/observability.md "Compile
account") for the set-up a run of the cell pays, in ONE process:

    python3 benchmark/tools/compile_sites.py <workload> <seed> [--fences N] [--rehearse-on-cpu]

The cell's set-up through the driver's own functions (the device, the rows,
the ETL session and its query, the model; in a ``fit`` cell part (a) too,
which a DLRM run pays inside ``setup_s``; in the language-model cells part (a)
is the benchmark's own work outside ``setup_s`` and is left out), its
``warm_up`` fit, then the window's fit in a thread until ``N`` epoch fences
(default 2). Then, from the account:

- every ``estimator.compile`` site by fit (``warm-up`` / ``window``) with its
  wall split into trace / lower / XLA compile / cache load / rest, programs,
  cache hits and misses;
- what compiled under no site, by name, with the obs span it ran under;
- what compiled after a fit's first fence (late), by name;
- the fits' residue (``estimator.fit.first_fence_seconds`` /
  ``.unaccounted_seconds``) and the process totals;
- how many ``jax.monitoring`` events the set-up fired and the seconds the
  account's two listeners took in all of them (timed HERE, around the
  listeners: the program times nothing of the kind).

Run it twice in one chiprun call to read a cell compiled anew and then warm
(the second run finds what the first wrote to the machine's cache, where the
cache takes it). One JSON line at the end, also appended to
``chiprun_out/compile_sites.jsonl``. Decides nothing."""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
os.environ.setdefault("RAYDP_TPU_NO_GLOBAL_ZYGOTE", "1")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")

PARTS = ("trace_s", "lower_s", "backend_s", "cache_load_s", "rest_s")


def time_the_listeners(profiler) -> dict:
    """Swap the account's two listeners for wrappers that count the calls
    and sum the time inside them; returns the tally they fill."""
    import jax

    tally = {"events": 0, "listener_s": 0.0}

    def timed(listener):
        def call(*args, **kwargs):
            t0 = time.perf_counter()
            listener(*args, **kwargs)
            tally["listener_s"] += time.perf_counter() - t0
            tally["events"] += 1

        return call

    jax.monitoring.unregister_event_listener(profiler._on_compile_event)
    jax.monitoring.unregister_event_duration_listener(
        profiler._on_compile_duration)
    jax.monitoring.register_event_listener(timed(profiler._on_compile_event))
    jax.monitoring.register_event_duration_secs_listener(
        timed(profiler._on_compile_duration))
    return tally


def set_up(ctx, driver, seed: int):
    """(module, train, held, warm_up(), make_estimator) through the
    driver's own functions, by the driver's kind."""
    lmpretrain = getattr(driver, "lmpretrain", driver)
    start = getattr(driver, "_start_placed", None) or getattr(
        lmpretrain, "_start", None)
    if start is not None:  # the lmpretrain family: one _start for all
        model_class = lmpretrain._named(ctx.config["model"]["class"])
        _, train, held, _, _, _, module, _ = start(ctx, model_class, seed)
        return (module, train, held,
                lambda: lmpretrain.warm_up(ctx, module, train, held, seed),
                lmpretrain.lmfit.make_estimator)
    ctx.claim_device()
    tr = ctx.traffic
    if hasattr(driver, "check_arithmetic"):  # kind fit (DLRM)
        from benchmark.harness import criteo

        m = ctx.config["model"]
        table, raw = criteo.raw_frame(
            seed, int(tr["rows"]), m["num_dense"], m["vocab_sizes"],
            float(tr["zipf_a"]))
        session, parallelism = driver.start_etl(ctx)
        train_df, test_df, _ = driver.preprocess(
            ctx, session, table, parallelism, seed)
        del table, raw
        train = train_df.limit(int(tr["train_rows"]))
        held = test_df.limit(int(tr["held_out_rows"]))
        module = driver.make_model(ctx)
        a = driver.check_arithmetic(
            ctx, module, driver.held_out_arrays(ctx, held), seed)
        return (module, train, held,
                lambda: driver.warm_up(ctx, module, train, held, seed,
                                       a["initial_held_out_loss"]),
                driver.make_estimator)
    from benchmark.harness import tokens  # kind lmfit (the looped LM)

    table, _ = tokens.raw_frame(
        seed, int(tr["rows"]), int(tr["seq_len"]),
        int(ctx.config["vocab_size"]), float(tr["zipf_a"]),
        float(tr["bigram_tilt"]))
    session = driver.start_etl(ctx)
    train, held, _ = driver.preprocess(ctx, session, table, seed)
    module = driver.make_model(ctx)
    return (module, train, held,
            lambda: driver.warm_up(ctx, module, train, held, seed),
            driver.make_estimator)


def table(ctx, head, rows) -> None:
    widths = [max(len(str(r[i])) for r in [head] + rows)
              for i in range(len(head))]
    for row in [head] + rows:
        ctx.say("  ".join(str(v).rjust(w) if i else str(v).ljust(w)
                          for i, (v, w) in enumerate(zip(row, widths))))


def report(ctx, account: dict, window_fits, snapshot: dict, tally: dict,
           timed: bool) -> dict:
    """The tables, and the line that holds them. A rehearsal prints no
    time: names and counts only."""
    def s(value):
        if value is None:
            return "open"
        return f"{value:.3f}" if timed else "-"

    rows = []
    for site in account["sites"]:
        label = "window" if site["fit"] in window_fits else "warm-up"
        rows.append([f"{label}#{site['fit']}", site["what"], s(site["wall_s"])]
                    + [s(site[p]) for p in PARTS]
                    + [site["programs"], site["cache_hits"],
                       site["cache_misses"]])
    ctx.say("estimator.compile sites (seconds):")
    table(ctx, ["fit", "what", "wall", "trace", "lower", "backend",
                "cache_load", "rest", "programs", "hits", "misses"], rows)
    ctx.say("compiled under no site (jax.compile.outside_*), by name:")
    table(ctx, ["fun_name", "seconds", "programs", "under"],
          [[name, s(row["seconds"]), row["programs"], row["under"]]
           for name, row in account["outside"].items()])
    ctx.say("compiled after a fit's first fence (estimator.compile.late_*): "
            + ("none" if not account["late"] else ""))
    if account["late"]:
        table(ctx, ["fun_name", "seconds", "programs", "under", "epoch",
                    "fit"],
              [[r["fun_name"], s(r["seconds"]), r["programs"], r["under"],
                r["epoch"], r["fit"]] for r in account["late"]])
    counters = {
        name: snapshot[name]["value"] for name in sorted(snapshot)
        if name.startswith(("estimator.compile", "jax.compile.",
                            "estimator.fit.", "exchange.stage_seconds"))}
    line = {"workload": ctx.cell.name, "seed": ctx.seed,
            "events": tally["events"], "account": account}
    if timed:
        ctx.say("counters: " + ", ".join(
            f"{k}={v:.3f}" for k, v in counters.items()))
        ctx.say(f"jax.monitoring events the account's listeners saw: "
                f"{tally['events']}; their summed time: "
                f"{tally['listener_s'] * 1e3:.2f} ms")
        line.update(counters=counters, listener_s=tally["listener_s"],
                    device=ctx.device)
    else:
        ctx.say(f"jax.monitoring events the account's listeners saw: "
                f"{tally['events']}")
        line["account"] = {
            "sites": [[site["fit"], site["what"], site["programs"]]
                      for site in account["sites"]],
            "outside": sorted(account["outside"]),
            "late": [r["fun_name"] for r in account["late"]]}
    return line


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("seed", type=int)
    ap.add_argument("--fences", type=int, default=2)
    ap.add_argument("--rehearse-on-cpu", action="store_true")
    args = ap.parse_args()
    if args.rehearse_on_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"

    from benchmark.harness import cells
    from benchmark.harness.child import Ctx
    from raydp_tpu import obs
    from raydp_tpu.compile_cache import enable_compile_cache
    from raydp_tpu.obs import profiler

    if not hasattr(profiler, "compile_account"):
        print("this program keeps no compile account "
              "(obs.profiler.compile_account)", file=sys.stderr)
        return 2
    cell = cells.resolve(ROOT, args.workload)
    driver = cells.load_module(cell.driver_path, f"traffic kind {cell.kind!r}")
    ctx = Ctx(ROOT, cell, "compile_sites", args.seed, 0.0, False,
              args.rehearse_on_cpu, tempfile.mkdtemp(prefix="raydp-sites-"))
    code = 1
    try:
        # the account starts where the driver's does: claim_device enables
        # the cache (a second call registers nothing)
        enable_compile_cache()
        tally = time_the_listeners(profiler)
        t0 = time.perf_counter()
        module, train, held, warm_up, make_estimator = set_up(
            ctx, driver, args.seed)
        warm_up()
        est = make_estimator(ctx, module, args.seed, num_epochs=1_000_000)
        failure = []

        def job():
            try:
                est.fit_on_etl(train, held)
            except BaseException as exc:  # noqa: BLE001 - reported below
                failure.append(exc)

        threading.Thread(target=job, name="window-fit", daemon=True).start()
        while len(est.history) < args.fences and not failure:
            time.sleep(0.01)
        if failure:
            raise RuntimeError(f"the window's fit failed: {failure[0]!r}")
        ctx.say_time(f"set-up, the warm-up fit and the window's fit to its "
                     f"fence {args.fences}", time.perf_counter() - t0)
        window_fits = {s["fit"] for s in est.compile_account()["sites"]}
        line = report(ctx, profiler.compile_account(), window_fits,
                      obs.metrics.snapshot(), tally, not args.rehearse_on_cpu)
        out_dir = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        prefix = "[REHEARSAL on cpu - not a chip run] " if (
            args.rehearse_on_cpu) else ""
        with open(os.path.join(out_dir, "compile_sites.jsonl"), "a") as log:
            log.write(json.dumps({"rehearsal": args.rehearse_on_cpu, **line})
                      + "\n")
        print(prefix + json.dumps(line), flush=True)
        code = 0
    except BaseException:  # noqa: BLE001 - printed, then the process leaves
        import traceback

        traceback.print_exc()
    finally:
        try:
            import raydp_tpu
            from raydp_tpu.cluster import api as cluster

            raydp_tpu.stop_etl()
            cluster.shutdown()
        finally:
            os._exit(code)


if __name__ == "__main__":
    sys.exit(main())
