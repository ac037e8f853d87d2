#!/usr/bin/env python3
"""Record the small trace the reduction is tested against (run on the chip):

    chiprun -- python3 benchmark/tests/record_trace.py

Three calls of the fused interaction kernel and of one matmul, each fenced,
with 20 ms of host sleep between them, inside the ``bench.trace_window``
annotation. Writes ``chiprun_out/benchmark/small_trace.xplane.pb`` and a
listing of what it holds; the file kept under ``benchmark/tests/data`` is a
copy of one such recording, and ``test_bench_trace.py`` states what it must
reduce to (three kernel calls, three matmuls, busy far below the window)."""

from __future__ import annotations

import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import xplane
    from raydp_tpu.ops.interaction import dot_interaction_pallas

    assert jax.devices()[0].platform == "tpu", jax.devices()
    rng = np.random.default_rng(0)
    stacked = jnp.asarray(rng.standard_normal((2048, 27, 16)), jnp.float32)
    a = jnp.asarray(rng.standard_normal((1024, 1024)), jnp.bfloat16)
    interact = jax.jit(dot_interaction_pallas)
    matmul = jax.jit(lambda a: a @ a)
    jax.block_until_ready((interact(stacked), matmul(a)))
    out = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out, exist_ok=True)
    trace_dir = os.path.join(out, "small_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_EVENT):
        time.sleep(0.02)  # device and host clocks agree to a fraction of a ms
        for _ in range(3):
            jax.block_until_ready(interact(stacked))
            jax.block_until_ready(matmul(a))
            time.sleep(0.02)
    jax.profiler.stop_trace()
    path = xplane.find_xplane(trace_dir)
    shutil.copy(path, os.path.join(out, "small_trace.xplane.pb"))
    print("recorded", path, os.path.getsize(path), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
