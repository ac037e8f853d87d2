#!/usr/bin/env python3
"""Time the expert layer alone on the chip, one implementation of the grouped
product after another (``ops/experts.IMPLS``) and one rows' bound after
another, forward + backward at the cell's shapes:

    chiprun -- python3 benchmark/tools/moe_layer_bench.py [tokens]

32,768 tokens of width 2048, 8 experts of width 1792 held of 32, top-4; 15 %
of the tokens are one vector (the commonest id of a Zipf(1.1) batch routes
alike), so the groups are uneven. Prints, per variant, the wall time of a
call (after two warm-up calls, the mean of five), the load, and the ten
longest operations of a traced call. How ``IMPL_WHY`` was decided (PR 34)."""

from __future__ import annotations

import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness import xplane
    from raydp_tpu.ops import experts

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 32768
    d, f, held, total, k = 2048, 1792, 8, 32, 4
    print("device", jax.devices()[0].device_kind, flush=True)
    rng = np.random.default_rng(0)
    u = rng.standard_normal((n, d)).astype(np.float32)
    u[rng.random(n) < 0.15] = u[0]
    u = jnp.asarray(u, jnp.bfloat16)
    w_gate = jnp.asarray(0.02 * rng.standard_normal((d, total)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.05, 0.05, total), jnp.float32)
    w13 = jnp.asarray(0.02 * rng.standard_normal((held, d, 2 * f)), jnp.float32)
    w2 = jnp.asarray(0.02 * rng.standard_normal((held, f, d)), jnp.float32)
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", "moe_layer")
    for impl in experts.IMPLS:
        for share in (1.0, 0.5):
            bound = experts.row_bound_for(int(n * k * share))

            def loss(u, w_gate, w13, w2):
                out, report = experts.routed_experts(
                    u, w_gate, bias, w13, w2, first=0, top_k=k,
                    row_bound=bound, impl=impl)
                return (out * out).sum(), report

            run = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                                             has_aux=True))
            try:
                for _ in range(2):
                    (_, report), grads = jax.block_until_ready(
                        run(u, w_gate, w13, w2))
            except Exception as exc:  # noqa: BLE001 - say which one failed
                print(f"{impl} share {share}: FAILED {str(exc)[:400]}",
                      flush=True)
                continue
            t0 = time.perf_counter()
            for _ in range(5):
                jax.block_until_ready(run(u, w_gate, w13, w2))
            ms = (time.perf_counter() - t0) / 5 * 1e3
            load = np.asarray(report["load"])
            print(f"{impl} rows {bound} (share {share}): {ms:.2f} ms a call "
                  f"(forward + backward); load {load.tolist()} = "
                  f"{load.sum() / (n * k):.3f} of the pairs, dropped "
                  f"{float(report['dropped']):.0f}; finite "
                  f"{bool(all(np.isfinite(np.asarray(g, np.float32)).all() for g in grads))}",
                  flush=True)
            trace_dir = os.path.join(out_dir, f"{impl}_{share}")
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation(xplane.WINDOW_EVENT):
                jax.block_until_ready(run(u, w_gate, w13, w2))
            jax.profiler.stop_trace()
            summary = xplane.reduce_trace(xplane.find_xplane(trace_dir))
            print(f"  traced call: busy {summary.busy_s * 1e3:.2f} ms; "
                  + "; ".join(f"{name} {s * 1e3:.2f}"
                              for name, s in summary.device_ops), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
