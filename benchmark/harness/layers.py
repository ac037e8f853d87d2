"""Per-layer readers. Each metric has a file of its own under
``layer_metrics/``: a ``.json`` that names one of the reader kinds below and
its parameters, or a ``.py`` with ``read(sources) -> float | None``. A
reader that finds nothing to read returns None and the metric is left out of
the line.

``sources`` is what a driver hands over after its window:

``histograms``  {name: {"count": n, "sum": s}}   window deltas of the program's histograms
``counters``    {name: delta}                    window deltas of the program's counters
``values``      {name: number}                   what the driver read from the program's
                                                 stats objects or timed itself
``trace``       harness.xplane.TraceSummary | None
``kernels``     {cost name: {"cost": {...}, ...}} needed ops/bytes per call of a kernel
``peaks``       the peaks row of this device
"""

from __future__ import annotations

import json
import re
from typing import Optional

from . import costs, xplane
from .cells import load_module


def histogram_totals(names) -> dict:
    """{name: {"count", "sum"}} of the program's histograms, as they stand
    (cumulative); two of these bracket a window."""
    from raydp_tpu import obs

    snap = obs.metrics.snapshot()
    return {name: {"count": snap.get(name, {}).get("count", 0),
                   "sum": snap.get(name, {}).get("sum", 0.0)}
            for name in names}


def histogram_deltas(before: dict, after: dict) -> dict:
    """The ``histograms`` source: what was observed between two totals."""
    return {name: {"count": after[name]["count"] - before[name]["count"],
                   "sum": after[name]["sum"] - before[name]["sum"]}
            for name in after}


def _histogram(spec, sources):
    delta = sources.get("histograms", {}).get(spec["metric"])
    if not delta or not delta.get("count"):
        return None
    if spec.get("stat", "mean") != "mean":
        raise ValueError("histogram readers give the window's mean only")
    return delta["sum"] / delta["count"] * spec.get("scale", 1.0)


def _counter(spec, sources):
    value = sources.get("counters", {}).get(spec["metric"])
    return None if value is None else value * spec.get("scale", 1.0)


def _value(spec, sources):
    value = sources.get("values", {}).get(spec["key"])
    return None if value is None else value * spec.get("scale", 1.0)


def _trace_idle_share(spec, sources):
    trace = sources.get("trace")
    if trace is None or trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.busy_s / trace.window_s)


def _trace_kernel_roofline(spec, sources):
    """Share of the roofline of the device operations whose name matches
    ``op_pattern``: calls x least time per call over their summed device
    time. The driver supplies the needed cost per call under
    ``kernels[spec["cost"]]``."""
    trace = sources.get("trace")
    kernel = sources.get("kernels", {}).get(spec["cost"])
    if trace is None or kernel is None:
        return None
    pattern = re.compile(spec["op_pattern"])
    calls, seconds = 0, 0.0
    for name, (n, total) in trace.ops.items():
        if pattern.search(name):
            calls += n
            seconds += total
    if not calls or seconds <= 0:
        return None
    least = costs.roofline(kernel["cost"], sources["peaks"])["min_s"]
    return 100.0 * calls * least / seconds


def _trace_ops_ms_per_step(spec, sources):
    """Device milliseconds a step spends in the operations whose RESULT type
    (``xplane.result_type``: what the operation writes, not what it reads)
    matches ``result_pattern``: their summed device time over the steps
    traced (``xplane.steps_traced``)."""
    trace = sources.get("trace")
    if trace is None:
        return None
    pattern = re.compile(spec["result_pattern"])
    seconds = sum(total for name, (_, total) in trace.ops.items()
                  if pattern.search(xplane.result_type(name)))
    steps = xplane.steps_traced(trace.ops)
    if not steps or seconds <= 0:
        return None
    return 1e3 * seconds / steps


def _trace_busy_ms_per_step(spec, sources):
    """Device-busy milliseconds per step traced: everything the device did
    in the window (evaluation included) over the steps it holds."""
    trace = sources.get("trace")
    if trace is None:
        return None
    steps = xplane.steps_traced(trace.ops)
    if not steps or trace.busy_s <= 0:
        return None
    return 1e3 * trace.busy_s / steps


READERS = {
    "histogram": _histogram, "counter": _counter,
    "value": _value, "trace_idle_share": _trace_idle_share,
    "trace_kernel_roofline": _trace_kernel_roofline,
    "trace_ops_ms_per_step": _trace_ops_ms_per_step,
    "trace_busy_ms_per_step": _trace_busy_ms_per_step,
}


def read_metric(path: str, sources: dict) -> Optional[float]:
    if path.endswith(".py"):
        return load_module(path, "per-layer reader").read(sources)
    with open(path) as f:
        spec = json.load(f)
    kind = spec.get("reader")
    if kind not in READERS:
        raise ValueError(
            f"{path}: reader {kind!r} is not one of {sorted(READERS)}; a "
            "reader of another kind is a .py file with read(sources)"
        )
    return READERS[kind](spec, sources)


def read_all(cell, sources: dict) -> dict:
    """{name: {"value", "unit"}} for this cell's per-layer metrics that had
    something to read."""
    out = {}
    for metric in cell.per_layer:
        value = read_metric(cell.layer_files[metric["name"]], sources)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
