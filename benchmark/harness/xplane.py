"""Reduce a profiler trace (``.xplane.pb``) to device busy time, per-operation
time and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else. A device plane is one
whose name starts with ``/device:TPU:``; its operations are the events of its
``XLA Ops`` line, less the control-flow containers (``while``,
``conditional``, ``call``), whose events span their bodies' operations and
the stalls between them. The traced window is the host event ``bench.trace_window``
(a ``jax.profiler.TraceAnnotation`` the driver holds open while it traces):
device intervals are clipped to it, so busy time can never exceed the window.
Busy is the union of operation intervals per device, averaged over devices.
An idle gap is attributed to the shortest host event (any thread) that spans
at least half of it, failing that to the one that overlaps it longest, or to
``host: no profiler event`` (plain Python between jax calls).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
import shutil
from typing import Dict, List, Optional, Tuple

WINDOW_EVENT = "bench.trace_window"
DEVICE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
NO_HOST_EVENT = "host: no profiler event"
CONTAINERS = ("%while", "%conditional", "%call")


def result_type(op: str) -> str:
    """The result type of an operation's HLO line, layouts left out:
    ``f32[2048,16]`` or ``(f32[9,16], f32[9])``; "" where the line has none."""
    _, _, rest = op.partition(" = ")
    bare = re.sub(r"\{[^}]*\}", "", rest)
    tuple_type = re.match(r"\(([^()]*)\)", bare)
    if tuple_type:
        return f"({tuple_type.group(1)})"
    return bare.split(" ")[0].split("(")[0]


def short_name(op: str) -> str:
    """An operation's event name is its whole HLO line; for display keep the
    instruction's name and its result type: ``fusion.12 f32[2048,16]``."""
    head, _, rest = op.partition(" = ")
    if not rest:
        return op[:96]
    result = result_type(op)
    if result.startswith("("):
        result = f"({result[1:-1][:48]})"
    kind = re.search(r"custom_call_target=\"([^\"]+)\"", rest)
    return " ".join(x for x in (head.lstrip("%"), result,
                                kind.group(1) if kind else "") if x)[:96]


def steps_traced(ops: Dict[str, Tuple[int, float]]) -> int:
    """How many steps of the step program the window holds. A step runs every
    operation of its program once, so the call count that most distinct
    operations share is the number of steps (an evaluation's or an upload's
    operations run some other number of times and are far fewer). 0 where no
    operation ran twice."""
    counts: Dict[int, int] = {}
    for calls, _ in ops.values():
        if calls > 1:
            counts[calls] = counts.get(calls, 0) + 1
    return max(counts, key=lambda c: (counts[c], c)) if counts else 0


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # averaged over the device planes
    devices: int
    ops: Dict[str, Tuple[int, float]]  # full HLO line -> (calls, summed seconds), all devices
    device_ops: List[list]  # top 10 [name, seconds]
    idle_gaps: List[list]  # top 10 [host activity, seconds]

    def breakdown(self) -> dict:
        return {"device_ops": self.device_ops, "idle_gaps": self.idle_gaps}


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _clip(start: float, end: float, lo: float, hi: float):
    start, end = max(start, lo), min(end, hi)
    return (start, end) if end > start else None


def reduce_trace(path: str, device_prefix: str = DEVICE_PREFIX,
                 gaps_considered: int = 64) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device_planes, host_events = [], []
    window: Optional[Tuple[float, float]] = None
    for plane in data.planes:
        if plane.name.startswith(device_prefix):
            device_planes.append(plane)
            continue
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                span = (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
                if ev.name == WINDOW_EVENT:
                    window = span[:2]
                else:
                    host_events.append(span)
    if not device_planes:
        raise ValueError(
            f"{path}: no plane named {device_prefix}* — not a trace of this "
            "device")

    per_device: List[List[Tuple[float, float, str]]] = []
    for plane in device_planes:
        lines = [ln for ln in plane.lines if ln.name == OP_LINE]
        if not lines:
            raise ValueError(
                f"{path}: plane {plane.name} has no {OP_LINE!r} line (has "
                f"{[ln.name for ln in plane.lines]})")
        per_device.append([
            (ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
            for ln in lines for ev in ln.events
            if ev.duration_ns > 0 and not ev.name.startswith(CONTAINERS)
        ])
    if window is None:
        # no annotation: the window is what the device events span
        starts = [e[0] for evs in per_device for e in evs]
        ends = [e[1] for evs in per_device for e in evs]
        if not starts:
            raise ValueError(f"{path}: no device operation in the trace")
        window = (min(starts), max(ends))
    lo, hi = window

    ops: Dict[str, List[float]] = {}
    busy_total = 0.0
    gaps: List[Tuple[float, float]] = []
    for events in per_device:
        clipped = []
        for start, end, name in events:
            span = _clip(start, end, lo, hi)
            if span is None:
                continue
            clipped.append(span)
            slot = ops.setdefault(name, [0, 0.0])
            slot[0] += 1
            slot[1] += (span[1] - span[0]) * 1e-9
        merged = _union(clipped)
        busy_total += sum(e - s for s, e in merged) * 1e-9
        edges = [lo] + [t for span in merged for t in span] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]

    by_host: Dict[str, float] = {}
    for g0, g1 in sorted(gaps, key=lambda g: g[0] - g[1])[:gaps_considered]:
        # the innermost host event that spans most of the gap says most
        # (a thread's outer frames span everything); failing one, whatever
        # overlaps the gap longest
        best, best_overlap, inner = NO_HOST_EVENT, 0.0, None
        for h0, h1, name in host_events:
            overlap = min(g1, h1) - max(g0, h0)
            if overlap >= 0.5 * (g1 - g0) and (
                    inner is None or h1 - h0 < inner[0]):
                inner = (h1 - h0, name)
            if overlap > best_overlap:
                best, best_overlap = name, overlap
        if inner is not None:
            best = inner[1]
        by_host[best] = by_host.get(best, 0.0) + (g1 - g0) * 1e-9

    def top(table):
        return [[name, seconds] for name, seconds in
                sorted(table.items(), key=lambda kv: -kv[1])[:10]]

    return TraceSummary(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_total / len(per_device),
        devices=len(per_device),
        ops={name: (int(n), s) for name, (n, s) in ops.items()},
        device_ops=top({short_name(name): s for name, (_, s) in ops.items()}),
        idle_gaps=top(by_host),
    )


KEEP_ENV = "RAYDP_TPU_BENCH_KEEP_TRACE"


def keep_copy(trace_dir: str, ctx) -> None:
    """With ``RAYDP_TPU_BENCH_KEEP_TRACE=1`` a traced run leaves its
    ``.xplane.pb`` under ``chiprun_out/benchmark`` (for
    ``benchmark/tools/dump_trace.py``); otherwise traces die with the run."""
    if os.environ.get(KEEP_ENV) != "1":
        return
    out = os.path.join(ctx.root, "chiprun_out", "benchmark")
    os.makedirs(out, exist_ok=True)
    shutil.copy(find_xplane(trace_dir), os.path.join(
        out, f"{ctx.cell.name}.seed{ctx.seed}.xplane.pb"))
