#!/usr/bin/env python3
"""``lm_objective_gaps.py`` for a cell whose comparison has the FOURTH gap,
``token_loss_rms`` (traffic kinds ``lmpretrain_routed_tokens`` and
``lmpretrain_routed_placed`` without the placement): the same command line,
process, seeds and log,

    python3 benchmark/tools/lm_token_gaps.py <workload> <first seed> <seeds> [--budget-s S] [--rehearse-on-cpu]

with the objective's four gaps as run and matched through the cell's driver's
own ``_mode_gaps``, and the second reading, the reference ITSELF in bf16 from
end to end under the as-run routing, through its ``bf16_reference_gaps``:
``bf16_reference.refused_by`` names the ``as_run`` limits that refuse it and
must name one on every seed. No epoch is replayed. Decides nothing."""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.tools import lm_objective_gaps as base  # noqa: E402


def objective_only(ctx, lm, module, ref, est, train, seed):
    import gc
    import jax
    import jax.numpy as jnp
    import numpy as np

    tr = ctx.traffic
    batch = int(tr["batch"])
    order = np.asarray(est.epoch_order(0, len(train)))
    x = jnp.asarray(train[order[:batch]])
    cfg = ref.config_of(ctx.config)
    block = int(tr["reference_token_block"])
    params = jax.jit(lambda r: module.init(r, x, None, method="loss"))(
        jax.random.PRNGKey(seed % (2 ** 31)))
    _, groups = lm.lmfit.grad_groups(params)
    reference = lm.RoutedReference(ref, cfg, block)
    out = {}
    for mode in lm.MODES:
        if mode == "matched":
            variant = module.clone(dtype=jnp.float32)
            with jax.default_matmul_precision("highest"):
                got = lm._mode_gaps(ctx, variant, ref, cfg, reference,
                                    lm.lmfit._objective(variant), params, x,
                                    groups, block)
        else:
            got = lm._mode_gaps(ctx, module, ref, cfg, reference,
                                lm.lmfit._objective(module), params, x,
                                groups, block)
            exact, routing = got[5], got[6]
        gaps, chosen, _, finite, dropped = got[:5]
        out.update({f"{mode}.{k}": v for k, v in gaps.items()})
        out.update({f"{mode}.{k}": chosen[k] for k in lm.SELECTION})
        out[f"{mode}.pairs_dropped"] = dropped
        out[f"{mode}.finite"] = bool(finite)
        del got
        gc.collect()
    gaps = lm.bf16_reference_gaps(ref, cfg, reference, params, x, routing,
                                  exact, groups, block)
    limits = tr["arith_tolerance"]["as_run"]
    out.update({f"bf16_reference.{k}": v for k, v in gaps.items()})
    out["bf16_reference.refused_by"] = [
        k for k in lm.GAPS if gaps[k] > limits[k]]
    del exact, params
    gc.collect()
    return out


if __name__ == "__main__":
    base.objective_only = objective_only
    sys.exit(base.main())
