#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmark/run.py --workload <cell> --rehearse-on-cpu
    python3 benchmark/run.py --workload <cell> --check-seeds 11,12,13,...

Runs one cell of ``BENCHMARK.json`` on the machine it is started on and prints,
as the last line of standard output, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` in a
traced run). With ``--trace 0`` the metrics are the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics.

A chip belongs to one process, so THIS process never imports jax. The cell's
traffic kind (``benchmark/drivers/<kind>.py``) names the phases of a run; each
is one child process, one after another. A child that finds no TPU fails and
the run prints no result. Whatever a run starts carries a tag in its
environment; the parent waits for all of it to end and kills what is left
(process discipline copied from ``chip_smoke.py``, PR 21).

``--check-seeds`` runs the cell's correctness parts alone, once per listed
seed, prints each part's margin and no result line (exit code 0 when every
part held on every seed).

``--rehearse-on-cpu`` runs the same control flow at the tiny sizes each file
gives under ``rehearsal``, kernels interpreted, says so on every line and
prints no time, rate or utilization: its last line has empty ``metrics``.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import uuid

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark.harness import cells, procs  # noqa: E402
from benchmark.harness.child import (  # noqa: E402
    REHEARSAL_PREFIX, SEEDS_ENV, T0_ENV, Ctx)

TIME_LIMIT_S = 1150.0  # a first run, which compiles, may take 1200 s


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-on-cpu", action="store_true")
    p.add_argument("--check-seeds", default="",
                   help="comma-separated seeds: correctness parts only")
    p.add_argument("--child", help=argparse.SUPPRESS)
    p.add_argument("--workdir", help=argparse.SUPPRESS)
    return p.parse_args(argv)


def run_child(args) -> int:
    cell = cells.resolve(ROOT, args.workload)
    driver = cells.load_module(cell.driver_path, f"traffic kind {cell.kind!r}")
    ctx = Ctx(ROOT, cell, args.child, args.seed, args.seconds,
              bool(args.trace), args.rehearse_on_cpu, args.workdir)
    driver.run_phase(ctx)
    return 0


def merge(results: list) -> dict:
    """Phase results, in order, into one run result. ``correct`` is a dict of
    named parts per phase: the run is correct when every part of every phase
    is true."""
    out = {"metrics": {}, "device": {}, "attempted": 0, "failed": 0,
           "parts": {}, "breakdown": None}
    for res in results:
        out["metrics"].update(res.get("metrics", {}))
        out["device"].update(res.get("device", {}))
        out["attempted"] += int(res.get("attempted", 0))
        out["failed"] += int(res.get("failed", 0))
        out["parts"].update(res.get("correct", {}))
        if res.get("breakdown"):
            out["breakdown"] = res["breakdown"]
    out["correct"] = bool(out["parts"]) and all(out["parts"].values())
    return out


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.child:
        return run_child(args)
    t0 = time.time()
    if "jax" in sys.modules:
        raise AssertionError("the benchmark's parent must never import jax")
    if not os.path.isfile(os.path.join(ROOT, "raydp_tpu", "__init__.py")):
        print(f"benchmark/run.py: no raydp_tpu package in {ROOT}: the "
              "benchmark measures the program and cannot run without it",
              file=sys.stderr)
        return 2
    try:
        cell = cells.resolve(ROOT, args.workload)
        driver = cells.load_module(cell.driver_path,
                                   f"traffic kind {cell.kind!r}")
    except cells.CellError as exc:
        print(f"benchmark/run.py: {exc}", file=sys.stderr)
        return 2
    rehearsal = args.rehearse_on_cpu
    if args.seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args.seconds = float(json.load(f)["run_seconds"])

    tag = uuid.uuid4().hex
    workdir = tempfile.mkdtemp(prefix="raydp-bench-")
    env = dict(os.environ)
    env[procs.RUN_TAG_ENV] = tag
    env[T0_ENV] = repr(t0)
    # a machine-global zygote would outlive the run; the session-local one
    # dies with the phase that started it
    env["RAYDP_TPU_NO_GLOBAL_ZYGOTE"] = "1"
    env["PYTHONUNBUFFERED"] = "1"
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    # every program, however quick to compile, is found again by the next run
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    env.setdefault("TPU_LOG_DIR", "disabled")
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    if args.check_seeds:
        env[SEEDS_ENV] = args.check_seeds
    log_dir = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(log_dir, exist_ok=True)
    log_path = os.path.join(
        log_dir, f"{args.workload}.seed{args.seed}.trace{args.trace}"
                 f"{'.rehearsal' if rehearsal else ''}"
                 f"{'.check-seeds' if args.check_seeds else ''}.log")
    results, failed = [], None
    with open(log_path, "w") as log:

        def emit(line: str) -> None:
            print(line, flush=True)
            log.write(line + "\n")
            log.flush()

        try:
            for phase in (driver.check_phases() if args.check_seeds
                          else driver.phases(bool(args.trace))):
                remaining = TIME_LIMIT_S - (time.time() - t0)
                cmd = [sys.executable, os.path.abspath(__file__),
                       "--child", phase, "--workdir", workdir,
                       "--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds),
                       "--trace", str(args.trace)]
                if rehearsal:
                    cmd.append("--rehearse-on-cpu")
                proc = subprocess.Popen(
                    cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT, text=True, errors="replace",
                    start_new_session=True)
                killer = threading.Timer(max(remaining, 1.0), proc.kill)
                killer.start()
                try:
                    for line in proc.stdout:
                        emit(line.rstrip("\n"))
                    code = proc.wait()
                finally:
                    killer.cancel()
                result_path = os.path.join(workdir, f"{phase}.json")
                if code != 0 or not os.path.exists(result_path):
                    failed = f"phase {phase} failed (exit code {code})"
                    break
                with open(result_path) as f:
                    results.append(json.load(f))
                # what the phase started is gone before the next needs the chip
                left = procs.sweep(tag, grace_s=20.0)
                if left:
                    failed = f"phase {phase} left processes alive: {left}"
                    break
        finally:
            leftover = procs.sweep(tag, grace_s=5.0)
            shutil.rmtree(workdir, ignore_errors=True)
        if failed is None and leftover:
            failed = f"processes left alive at the end: {leftover} (killed)"
        if failed is not None:
            emit(f"benchmark/run.py: {failed} - no result")
            return 1
        run = merge(results)
        for part, ok in sorted(run["parts"].items()):
            emit(f"correct[{part}] = {ok}")
        if args.check_seeds:
            emit(f"check-seeds: every part held on every seed: {run['correct']}")
            return 0 if run["correct"] else 1
        if rehearsal:
            line = json.dumps({
                "rehearsal": True, "correct": run["correct"],
                "attempted": run["attempted"], "failed": run["failed"],
                "metrics": {}, "device": run["device"]})
            emit(REHEARSAL_PREFIX + "no time, rate or utilization is "
                 "printed by a rehearsal")
        else:
            line = cells.result_line(
                run["correct"], run["attempted"], run["failed"],
                run["metrics"], run["device"],
                run["breakdown"] if args.trace else None)
        log.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
