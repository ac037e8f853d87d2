#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, their lines, and per line the
operations that took most time. Look at a trace with this before writing a
per-layer reader against it.

    python3 benchmark/tools/dump_trace.py <file.xplane.pb | trace dir> [top N]
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import xplane  # noqa: E402


def main(argv) -> int:
    from jax.profiler import ProfileData

    path = argv[1]
    top = int(argv[2]) if len(argv) > 2 else 12
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        print(f"PLANE {plane.name}")
        for line in plane.lines:
            events = list(line.events)
            table = {}
            for ev in events:
                slot = table.setdefault(ev.name, [0, 0.0])
                slot[0] += 1
                slot[1] += ev.duration_ns * 1e-9
            print(f"  LINE {line.name}: {len(events)} events")
            for name, (n, s) in sorted(table.items(),
                                       key=lambda kv: -kv[1][1])[:top]:
                print(f"    {s:12.6f} s  x{n:<6d} {name[:150]}")
    if any(p.name.startswith(xplane.DEVICE_PREFIX) for p in data.planes):
        s = xplane.reduce_trace(path)
        print(f"REDUCED window {s.window_s:.6f} s busy {s.busy_s:.6f} s over "
              f"{s.devices} device(s)")
        print("  device_ops", s.device_ops)
        print("  idle_gaps", s.idle_gaps)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
