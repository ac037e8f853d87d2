#!/usr/bin/env python3
"""Record the small trace the expert layer's readers are tested against (run
on the chip):

    chiprun -- python3 benchmark/tests/record_moe_trace.py

Two fenced calls of the forward and backward expert layer
(``ops/experts.routed_experts``, the chip's default grouped product) at 2048
tokens of width 512, 2 experts of width 256 held of 8, top-2 (small, so that
the file stays small), and of one matmul that is no part of it, inside the
``bench.trace_window`` annotation. Writes
``chiprun_out/benchmark/moe_trace.xplane.pb`` and prints the pairs the held
experts were given; the file kept under ``benchmark/tests/data`` is a copy
of one such recording, and ``test_bench_lfm2.py`` states what the readers
must find in it."""

from __future__ import annotations

import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TOKENS, HIDDEN, WIDTH, HELD, TOTAL, TOP_K, CALLS = 2048, 512, 256, 2, 8, 2, 2


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import xplane
    from raydp_tpu.ops import experts

    assert jax.devices()[0].platform == "tpu", jax.devices()
    rng = np.random.default_rng(0)
    u = jnp.asarray(rng.standard_normal((TOKENS, HIDDEN)), jnp.bfloat16)
    w_gate = jnp.asarray(0.05 * rng.standard_normal((HIDDEN, TOTAL)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.05, 0.05, TOTAL), jnp.float32)
    w13 = jnp.asarray(0.05 * rng.standard_normal((HELD, HIDDEN, 2 * WIDTH)),
                      jnp.float32)
    w2 = jnp.asarray(0.05 * rng.standard_normal((HELD, WIDTH, HIDDEN)),
                     jnp.float32)
    w = jnp.asarray(rng.standard_normal((1024, 1024)), jnp.bfloat16)

    def loss(u, w_gate, w13, w2):
        out, report = experts.routed_experts(
            u, w_gate, bias, w13, w2, first=2, top_k=TOP_K)
        return (out * out).sum(), report

    layer = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3), has_aux=True))
    matmul = jax.jit(lambda w: w @ w)
    (_, report), _ = jax.block_until_ready(layer(u, w_gate, w13, w2))
    jax.block_until_ready(matmul(w))
    out = os.path.join(ROOT, "chiprun_out", "benchmark")
    os.makedirs(out, exist_ok=True)
    trace_dir = os.path.join(out, "moe_trace")
    shutil.rmtree(trace_dir, ignore_errors=True)
    jax.profiler.start_trace(trace_dir)
    with jax.profiler.TraceAnnotation(xplane.WINDOW_EVENT):
        for _ in range(CALLS):
            jax.block_until_ready(layer(u, w_gate, w13, w2))
            jax.block_until_ready(matmul(w))
    jax.profiler.stop_trace()
    path = xplane.find_xplane(trace_dir)
    shutil.copy(path, os.path.join(out, "moe_trace.xplane.pb"))
    print("recorded", path, os.path.getsize(path), "bytes; impl",
          experts.default_impl(), "; pairs held a call",
          np.asarray(report["load"]).tolist(), "; rows",
          experts.row_bound_for(TOKENS * TOP_K))
    return 0


if __name__ == "__main__":
    sys.exit(main())
