"""What every child process of a run shares: its arguments, the cell, where it
writes, how it claims the device, and the rule that a rehearsal prints no
time."""

from __future__ import annotations

import json
import os
import time

from . import cells

T0_ENV = "RAYDP_TPU_BENCH_T0"  # wall time at which the parent started
SEEDS_ENV = "RAYDP_TPU_BENCH_CHECK_SEEDS"  # run.py --check-seeds a,b,c
REHEARSAL_PREFIX = "[REHEARSAL on cpu - not a chip run] "


class Ctx:
    def __init__(self, root: str, cell: cells.Cell, phase: str, seed: int,
                 seconds: float, trace: bool, rehearsal: bool, workdir: str):
        self.root, self.cell, self.phase = root, cell, phase
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.rehearsal, self.workdir = rehearsal, workdir
        self.t0 = float(os.environ.get(T0_ENV, time.time()))
        self.device: dict = {}
        self.config = cells.sized(cell.config, rehearsal)
        self.traffic = cells.sized(cell.traffic, rehearsal)
        self.check_seeds = [
            int(x) for x in os.environ.get(SEEDS_ENV, "").split(",") if x]

    # -- reporting ------------------------------------------------------
    def say(self, msg: str) -> None:
        prefix = REHEARSAL_PREFIX if self.rehearsal else ""
        print(f"{prefix}[{self.phase}] {msg}", flush=True)

    def say_time(self, what: str, seconds: float) -> None:
        """A rehearsal prints no time, rate or utilization."""
        if not self.rehearsal:
            self.say(f"{what}: {seconds:.2f} s on {self.device.get('kind')} "
                     f"x{self.device.get('count')}")

    def since_start(self) -> float:
        return time.time() - self.t0

    # -- the device -----------------------------------------------------
    def claim_device(self) -> dict:
        """First thing a process that uses the device does: assert the
        platform and the chip count the cell asks for, enable the compile
        cache. No accelerator, no result."""
        import jax

        devices = jax.devices()
        self.device = {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
        }
        self.say(f"platform: {self.device['platform']}  device_kind: "
                 f"{self.device['kind']}  device count: "
                 f"{self.device['count']}  cpu count: {os.cpu_count()}")
        want = "cpu" if self.rehearsal else "tpu"
        if self.device["platform"] != want:
            raise SystemExit(
                f"jax.devices()[0].platform is {self.device['platform']!r}; "
                f"this run needs {want!r} - no accelerator, no result "
                "(--rehearse-on-cpu runs the tiny CPU rehearsal)")
        if not self.rehearsal and self.device["count"] < self.cell.chips:
            raise SystemExit(
                f"cell {self.cell.name} needs {self.cell.chips} chip(s), jax "
                f"sees {self.device['count']}")
        from raydp_tpu.compile_cache import enable_compile_cache

        self.say(f"compile cache: {enable_compile_cache()}")
        self.write("device", self.device)
        return self.device

    def memory_peak_bytes(self) -> int:
        import jax

        peak = 0
        for dev in jax.local_devices():
            stats = dev.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    # -- files the parent (and later phases) read -------------------------
    def path(self, name: str) -> str:
        return os.path.join(self.workdir, name)

    def write(self, name: str, obj) -> None:
        tmp = self.path(name + ".json.tmp")
        with open(tmp, "w") as f:
            json.dump(obj, f)
        os.replace(tmp, self.path(name + ".json"))

    def read(self, name: str):
        with open(self.path(name + ".json")) as f:
            return json.load(f)


def metric_dict(cell: cells.Cell, values: dict) -> dict:
    """{name: {"value", "unit"}} for this cell's end-to-end metrics, in
    ``BENCHMARK.json``'s units; a metric the driver did not produce is an
    error (every run reports every end-to-end metric of its cell)."""
    out = {}
    for metric in cell.end_to_end:
        if metric["name"] not in values:
            raise KeyError(
                f"driver produced no value for end-to-end metric "
                f"{metric['name']!r} of cell {cell.name}")
        out[metric["name"]] = {
            "value": float(values[metric["name"]]), "unit": metric["unit"]}
    return out
