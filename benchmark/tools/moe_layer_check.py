#!/usr/bin/env python3
"""The expert layer against every held expert on every token under a 0/1
mask (float32, highest), ON THE CHIP, at the published widths and fewer
tokens: each implementation of the grouped product, with bf16 operands and
with float32 ones at ``highest`` (the benchmark's ``matched`` run), inside a
recomputed block as the model runs it (``jax.checkpoint`` keeping
``ops.experts.KEPT``), forward and every gradient:

    chiprun -- python3 benchmark/tools/moe_layer_check.py [tokens]

Prints each variant's largest gaps (relative to the reference's largest
value) and exits 1 where a float32 variant is off by 1e-4 or a bf16 one by
5e-2, or a gradient is not finite."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raydp_tpu.ops import experts

    n = int(sys.argv[1]) if len(sys.argv) > 1 else 4096
    d, f, held, total, k, first = 2048, 1792, 8, 32, 4, 8
    print("device", jax.devices()[0].device_kind, flush=True)
    rng = np.random.default_rng(0)
    u = rng.standard_normal((n, d)).astype(np.float32)
    u[rng.random(n) < 0.15] = u[0]
    u = jnp.asarray(jnp.asarray(u, jnp.bfloat16), jnp.float32)
    w_gate = jnp.asarray(0.02 * rng.standard_normal((d, total)), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.05, 0.05, total), jnp.float32)
    w13 = jnp.asarray(0.02 * rng.standard_normal((held, d, 2 * f)), jnp.float32)
    w2 = jnp.asarray(0.02 * rng.standard_normal((held, f, d)), jnp.float32)
    probe = jnp.asarray(rng.standard_normal((n, d)), jnp.float32)

    def masked(u, w_gate, w13, w2):
        scores = jax.nn.sigmoid(u @ w_gate)
        _, sel = jax.lax.top_k(scores + bias, k)
        w = jnp.take_along_axis(scores, sel, axis=-1)
        w = w / (w.sum(-1, keepdims=True) + 1e-6)
        out = jnp.zeros_like(u)
        for e in range(held):
            h = u @ w13[e]
            y = (jax.nn.silu(h[:, :f]) * h[:, f:]) @ w2[e]
            out = out + jnp.where(sel == first + e, w, 0).sum(-1)[:, None] * y
        return (out * probe).sum(), out

    with jax.default_matmul_precision("highest"):
        (_, want), want_grads = jax.jit(jax.value_and_grad(
            masked, argnums=(0, 1, 2, 3), has_aux=True))(u, w_gate, w13, w2)
    bad = False
    for impl in experts.IMPLS:
        for dtype, precision, limit in ((jnp.float32, "highest", 1e-4),
                                        (jnp.bfloat16, None, 5e-2)):
            @jax.checkpoint
            def layer(u, w_gate, w13, w2):
                return experts.routed_experts(
                    u.astype(dtype), w_gate, bias, w13, w2, first=first,
                    top_k=k, impl=impl)

            layer = jax.checkpoint(
                layer.__wrapped__,
                policy=jax.checkpoint_policies.save_only_these_names(
                    experts.KEPT))

            def loss(u, w_gate, w13, w2):
                out, report = layer(u, w_gate, w13, w2)
                return (out * probe).sum(), (out, report)

            try:
                with jax.default_matmul_precision(precision):
                    (_, (out, report)), grads = jax.jit(jax.value_and_grad(
                        loss, argnums=(0, 1, 2, 3), has_aux=True))(
                            u, w_gate, w13, w2)
            except Exception as exc:  # noqa: BLE001 - say which one failed
                print(f"{impl} {jnp.dtype(dtype).name}: FAILED "
                      f"{str(exc)[:500]}", flush=True)
                bad = True
                continue
            gaps = [float(jnp.abs(a.astype(jnp.float32) - b).max()
                          / jnp.abs(b).max())
                    for a, b in zip((out,) + grads, (want,) + want_grads)]
            finite = all(bool(jnp.isfinite(g.astype(jnp.float32)).all())
                         for g in grads)
            ok = finite and max(gaps) <= limit
            bad = bad or not ok
            print(f"{impl} {jnp.dtype(dtype).name}: out {gaps[0]:.3g}, d u "
                  f"{gaps[1]:.3g}, d w_gate {gaps[2]:.3g}, d w13 {gaps[3]:.3g}"
                  f", d w2 {gaps[4]:.3g} (limit {limit}); finite {finite}; "
                  f"load {np.asarray(report['load']).tolist()}: "
                  f"{'ok' if ok else 'FAIL'}", flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
