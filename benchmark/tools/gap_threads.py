#!/usr/bin/env python3
"""Which thread's events lie over a trace's longest device idle gaps.

``xplane.reduce_trace`` names ONE host event a gap (the shortest that spans
half of it), whichever thread it ran on. This prints, for each of the longest
gaps inside the traced window, every host event that covers a tenth of it or
more with its thread's line (name#place in the plane), the innermost first,
so that an event that merely coincides with the gap on another thread can be
told from the thread that kept the device waiting.

    python3 benchmark/tools/gap_threads.py <file.xplane.pb | trace dir> [gaps N] [events M]
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
    n_gaps = int(argv[2]) if len(argv) > 2 else 6
    n_events = int(argv[3]) if len(argv) > 3 else 14
    if os.path.isdir(path):
        path = xplane.find_xplane(path)
    data = ProfileData.from_file(path)
    window, busy, host = None, [], []
    for plane in data.planes:
        # python threads' lines all read "python3": told apart by their place
        for nth, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.duration_ns <= 0:
                    continue
                span = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if plane.name.startswith(xplane.DEVICE_PREFIX):
                    if (line.name == xplane.OP_LINE
                            and not ev.name.startswith(xplane.CONTAINERS)):
                        busy.append(span)
                elif plane.name.startswith("/host:"):
                    if ev.name == xplane.WINDOW_EVENT:
                        window = span
                    else:
                        host.append((*span, f"{line.name}#{nth}", ev.name))
    if window is None or not busy:
        print("no traced window or no device operation in", path)
        return 1
    lo, hi = window
    merged = xplane._union([s for s in (xplane._clip(a, b, lo, hi)
                                        for a, b in busy) if s])
    edges = [lo] + [t for span in merged for t in span] + [hi]
    gaps = sorted(((edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                   if edges[i + 1] > edges[i]), key=lambda g: g[0] - g[1])
    idle = sum(b - a for a, b in gaps)
    print(f"window {(hi - lo) * 1e-6:.3f} ms, idle {idle * 1e-6:.3f} ms in "
          f"{len(gaps)} gaps")
    for g0, g1 in gaps[:n_gaps]:
        print(f"GAP {(g1 - g0) * 1e-6:9.3f} ms at +{(g0 - lo) * 1e-6:.3f} ms")
        over = sorted(
            ((min(g1, h1) - max(g0, h0), h0, h1, line, name)
             for h0, h1, line, name in host
             if min(g1, h1) - max(g0, h0) >= 0.1 * (g1 - g0)),
            key=lambda e: (e[2] - e[1]))  # the innermost (shortest) first
        for overlap, h0, h1, line, name in over[:n_events]:
            print(f"    {overlap * 1e-6:8.3f} ms of it under [{line}] "
                  f"{name[:90]} ({(h1 - h0) * 1e-6:.3f} ms, from "
                  f"{(h0 - g0) * 1e-6:+.3f} ms of the gap's start)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
