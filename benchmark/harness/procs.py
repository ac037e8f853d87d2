"""Process discipline, copied from ``chip_smoke.py`` (PR 21): every process
of a run inherits a tag in its environment; the parent waits for all of them
and kills what is left."""

from __future__ import annotations

import os
import time
from typing import List

RUN_TAG_ENV = "RAYDP_TPU_BENCH_RUN"


def tagged_pids(tag: str) -> List[int]:
    needle = f"{RUN_TAG_ENV}={tag}".encode()
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                env = f.read()
            with open(f"/proc/{entry}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except OSError:
            continue  # exited between listdir and open
        if needle in env.split(b"\0") and state != "Z":
            found.append(int(entry))
    return found


def sweep(tag: str, grace_s: float) -> List[int]:
    """Wait up to ``grace_s`` for the run's processes to exit, kill the
    rest, wait for those too. Returns the pids that had to be killed."""
    deadline = time.monotonic() + grace_s
    while True:
        alive = tagged_pids(tag)
        if not alive or time.monotonic() > deadline:
            break
        time.sleep(0.1)
    for pid in alive:
        try:
            os.kill(pid, 9)
        except ProcessLookupError:
            pass
    deadline = time.monotonic() + 10.0
    while alive and tagged_pids(tag) and time.monotonic() < deadline:
        time.sleep(0.1)
    return alive
