#!/usr/bin/env python3
"""Process hygiene of a benchmark run, measured from outside it:

    python3 benchmark/tools/watch_run.py <label> -- <arguments of benchmark/run.py>

runs ``benchmark/run.py`` with those arguments and, once a second while it
runs, reads ``/proc`` for every process that was not there before: its
command line and its resident set. When ``run.py`` has returned it lists, 0,
2 and 10 s later, the processes that carry a run's tag in their environment
(``harness/procs.RUN_TAG_ENV``, any tag) and any other process of the machine
that was not there before the run. One JSON line to standard output and to
``chiprun_out/watch_run.jsonl``: ``wall_s``, ``exit``, ``result`` (the run's
last line), ``rss_gib`` ({phase or command: its largest resident set}),
``rss_peak_at_s`` (the second of the run at which the three largest reached it:
hold it against the timed lines of the run's log), ``rss_gib_every_10_s``
(the largest process's resident set every tenth second) and
``left`` ({"0": [...], "2": [...], "10": [...]}: none is the only good
answer). PR 44 and PR 26 were refused for a process left running; this is
how a builder sees what the driver would. Decides nothing."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness.procs import RUN_TAG_ENV  # noqa: E402


def _pids() -> set:
    return {int(e) for e in os.listdir("/proc") if e.isdigit()}


def _read(pid: int, name: str) -> bytes:
    try:
        with open(f"/proc/{pid}/{name}", "rb") as f:
            return f.read()
    except OSError:
        return b""


def _what(pid: int) -> str:
    """A phase's name where the command line gives one, else the command."""
    words = _read(pid, "cmdline").decode(errors="replace").split("\0")
    if "--child" in words[:-1]:
        return "phase " + words[words.index("--child") + 1]
    return " ".join(w for w in words if w)[:120]


def _rss_bytes(pid: int) -> int:
    for line in _read(pid, "status").splitlines():
        if line.startswith(b"VmRSS:"):
            return int(line.split()[1]) * 1024
    return 0


def _alive(pid: int) -> bool:
    stat = _read(pid, "stat").decode(errors="replace")
    return bool(stat) and stat.rsplit(")", 1)[1].split()[0] != "Z"


def _left(before: set) -> list:
    me = os.getpid()
    out = []
    for pid in sorted(_pids() - before - {me}):
        if not _alive(pid):
            continue
        tagged = any(v.startswith(RUN_TAG_ENV.encode() + b"=")
                     for v in _read(pid, "environ").split(b"\0"))
        out.append({"pid": pid, "tagged": tagged, "what": _what(pid)})
    return out


def main() -> int:
    label, dashes, rest = sys.argv[1], sys.argv[2], sys.argv[3:]
    if dashes != "--":
        raise SystemExit(__doc__)
    before = _pids()
    t0 = time.time()
    with tempfile.TemporaryFile("w+") as said:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(ROOT, "benchmark", "run.py"), *rest],
            stdout=said, cwd=ROOT)
        before.add(proc.pid)
        rss: dict = {}
        peak_at: dict = {}
        series: list = []  # the largest process's GiB, every tenth second
        while proc.poll() is None:
            largest = 0
            for pid in _pids() - before - {os.getpid()}:
                size = _rss_bytes(pid)
                largest = max(largest, size)
                what = _what(pid) if size else ""
                if size > rss.get(what, 0):
                    rss[what] = size
                    peak_at[what] = round(time.time() - t0)
            if round(time.time() - t0) // 10 >= len(series):
                series.append(round(largest / 2 ** 30, 1))
            time.sleep(1.0)
        wall = time.time() - t0
        said.seek(0)
        last = said.read()[-20000:]
    left, t_end = {}, time.time()
    for after in (0, 2, 10):
        time.sleep(max(0.0, t_end + after - time.time()))
        left[str(after)] = _left(before)
    lines = [ln for ln in last.splitlines() if ln.strip()]
    try:
        result = json.loads(lines[-1]) if lines else None
    except ValueError:
        result = lines[-1][:400]
    line = {
        "label": label, "args": rest, "wall_s": round(wall, 1),
        "exit": proc.returncode, "result": result,
        "rss_gib": {k: round(v / 2 ** 30, 2) for k, v in sorted(
            rss.items(), key=lambda kv: -kv[1])[:8]},
        "rss_peak_at_s": {k: peak_at[k] for k, _ in sorted(
            rss.items(), key=lambda kv: -kv[1])[:3]},
        "rss_gib_every_10_s": series, "left": left}
    text = json.dumps(line)
    print(text, flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "watch_run.jsonl"), "a") as f:
        f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
