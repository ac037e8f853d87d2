"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell is ``<configuration>.<traffic mix>``. Its configuration is the file
``BENCHMARK.json`` lists for it; its mix is ``traffic/<mix>.json``; the mix
names its kind, whose driver is ``drivers/<kind>.py``; its per-layer metrics
are the ``layer_metrics/<name>.json|py`` files whose names ``BENCHMARK.json``
lists for this cell. A later PR adds files and entries, never code here; a
name that leads to no file is an error that says which file is missing."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class CellError(Exception):
    pass


def _load_json(path: str, what: str) -> dict:
    if not os.path.isfile(path):
        raise CellError(f"{what}: no file {path}")
    with open(path) as f:
        return json.load(f)


def load_module(path: str, what: str):
    if not os.path.isfile(path):
        raise CellError(f"{what}: no file {path}")
    name = "_bench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    kind: str
    driver_path: str
    end_to_end: List[dict]  # this cell's entries of BENCHMARK.json
    per_layer: List[dict]
    layer_files: Dict[str, str]  # metric name -> reader file


def _in_cell(metric: dict, cell_name: str) -> bool:
    return "workloads" not in metric or cell_name in metric["workloads"]


def resolve(root: str, workload: str, bench_dir: str = None) -> Cell:
    """``root`` is the checkout (holds ``BENCHMARK.json``); ``bench_dir`` the
    benchmark's own directory (default: the one this file lives in)."""
    bench_dir = bench_dir or BENCH_DIR
    bench = _load_json(os.path.join(root, "BENCHMARK.json"), "the benchmark")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(
            f"workload {workload!r} is not in BENCHMARK.json "
            f"(has: {', '.join(sorted(cells))})"
        )
    entry = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if entry["config"] not in configs:
        raise CellError(
            f"cell {workload}: configuration {entry['config']!r} is not "
            "under configs in BENCHMARK.json"
        )
    config = _load_json(
        os.path.join(root, configs[entry["config"]]["file"]),
        f"configuration {entry['config']!r}",
    )
    traffic = _load_json(
        os.path.join(bench_dir, "traffic", entry["traffic"] + ".json"),
        f"traffic mix {entry['traffic']!r}",
    )
    kind = traffic.get("kind")
    if not kind:
        raise CellError(f"traffic mix {entry['traffic']!r} names no kind")
    driver_path = os.path.join(bench_dir, "drivers", kind + ".py")
    if not os.path.isfile(driver_path):
        raise CellError(f"traffic kind {kind!r}: no file {driver_path}")
    per_layer = [m for m in bench["per_layer"] if _in_cell(m, workload)]
    layer_files = {}
    for metric in per_layer:
        base = os.path.join(bench_dir, "layer_metrics", metric["name"])
        for ext in (".json", ".py"):
            if os.path.isfile(base + ext):
                layer_files[metric["name"]] = base + ext
                break
        else:
            raise CellError(
                f"per-layer metric {metric['name']!r}: no file "
                f"{base}.json or {base}.py"
            )
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config_name=entry["config"], config=config,
        traffic_name=entry["traffic"], traffic=traffic, kind=kind,
        driver_path=driver_path,
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, workload)],
        per_layer=per_layer, layer_files=layer_files,
    )


def sized(section: dict, rehearsal: bool) -> dict:
    """A file's parameters, with its ``rehearsal`` overrides laid over them
    for the tiny CPU run (nested dicts merge one level deep)."""
    out = {k: v for k, v in section.items() if k != "rehearsal"}
    if rehearsal:
        for key, value in section.get("rehearsal", {}).items():
            if isinstance(value, dict) and isinstance(out.get(key), dict):
                out[key] = {**out[key], **value}
            else:
                out[key] = value
    return out


def result_line(
    correct: bool, attempted: int, failed: int, metrics: Dict[str, dict],
    device: Dict[str, Any], breakdown: dict = None,
) -> str:
    """The run's last line of standard output: exactly the keys the contract
    names (``breakdown`` only where a traced run has one)."""
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    return json.dumps(line)
