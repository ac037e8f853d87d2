#!/usr/bin/env python3
"""Record the small trace the scan's readers are tested against (run on the
chip):

    chiprun -- python3 benchmark/tests/record_ssd_trace.py

Two fenced calls of the forward and backward chunked scan (``ops/ssd.py``) at
1 x 1024 tokens, 8 heads of 64, state 128, chunk 256 (the published head,
state and chunk sizes; fewer heads and tokens, so that the file stays
small), and of one matmul that is no part of it, inside the
``bench.trace_window`` annotation. Writes
``chiprun_out/benchmark/ssd_trace.xplane.pb``; the file kept under
``benchmark/tests/data`` is a copy of one such recording, and
``test_bench_hybrid.py`` states what the readers must find in it."""

from __future__ import annotations

import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TOKENS, HEADS, HEAD_DIM, STATE, CHUNK, CALLS = 1024, 8, 64, 128, 256, 2


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import xplane
    from raydp_tpu.ops.ssd import ssd_chunk_scan

    assert jax.devices()[0].platform == "tpu", jax.devices()
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((1, TOKENS, HEADS, HEAD_DIM)), jnp.bfloat16)
    dt = jnp.asarray(rng.uniform(1e-3, 1e-1, (1, TOKENS, HEADS)), jnp.float32)
    a = -jnp.asarray(rng.uniform(1.0, 16.0, (HEADS,)), jnp.float32)
    b, c = (jnp.asarray(rng.standard_normal((1, TOKENS, STATE)), jnp.bfloat16)
            for _ in range(2))
    d = jnp.ones((HEADS,), jnp.float32)
    w = jnp.asarray(rng.standard_normal((1024, 1024)), jnp.bfloat16)
    scan = jax.jit(jax.grad(
        lambda *args: ssd_chunk_scan(*args, chunk=CHUNK).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4, 5)))
    matmul = jax.jit(lambda w: w @ w)
    jax.block_until_ready((scan(x, dt, a, b, c, d), matmul(w)))
    out = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out, exist_ok=True)
    trace_dir = os.path.join(out, "ssd_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_EVENT):
        for _ in range(CALLS):
            jax.block_until_ready(scan(x, dt, a, b, c, d))
            jax.block_until_ready(matmul(w))
    jax.profiler.stop_trace()
    path = xplane.find_xplane(trace_dir)
    shutil.copy(path, os.path.join(out, "ssd_trace.xplane.pb"))
    print("recorded", path, os.path.getsize(path), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
