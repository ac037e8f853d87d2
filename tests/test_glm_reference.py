"""``HybridLM`` of the ``glm4_moe_lite`` family against the benchmark's plain
reference (``benchmark/reference/glm_moe_lite``) at the configuration's
rehearsal sizes: the loss with both terms, the main and the module's logits,
every leaf's gradient and the selection, float32 matched and bf16 as run;
and the share test. (The tree, the two uses of the embedding and the head,
``from_config`` and the facts are ``test_glm_hybridlm.py``'s; the mutations
the comparison must catch ``test_glm_mutations.py``'s: a file each, so that
none passes the suite's two minutes a file.)"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import glm_hybrid_model as gm
from glm_hybrid_model import no_persistent_cache  # noqa: F401 - autouse
from glm_hybrid_model import MATCHED, model
from benchmark.reference import glm_moe_lite as ref
from raydp_tpu.ops import experts as experts_op


@pytest.fixture(scope="module")
def batch():
    return gm.batch()


def test_system_against_the_reference(batch):
    """Loss (both terms), both logits, every gradient and every choice,
    float32, of the five recomputed layers and the module as the cell builds
    them: latent attention through the flash kernels at keys of 24 over
    values of 16, the low-rank query, the shared expert, the top-2 of 16.
    (Two layers with full attention, not recomputed:
    ``test_glm_mutations.py``'s first case.)"""
    m = model(attn_impl="flash", remat=True)
    assert m.layer_types == ("mla",) * 5
    assert m.ffn_kinds == ("dense",) + ("experts",) * 4
    assert m.mtp_built and m.mtp_kinds == ("mla", "experts")
    p = gm.params(m, batch)
    got = gm.gaps(gm.program(m, p, batch), p, batch, ref.config_of(gm.CONFIG))
    assert all(g <= limit for g, limit in zip(got, MATCHED)), got


def test_system_as_run_in_bf16_against_the_reference(batch):
    """The cell's own precision (bf16 operands and stream; float32 router,
    norms, logits and losses) against the float32 reference under the
    program's routing: the rehearsal's ``as_run`` limits."""
    m = model(attn_impl="flash", remat=True, dtype=jnp.bfloat16)
    p = gm.params(m, batch)
    loss, mtp, logits, mtp_logits, grads, _, margin = gm.gaps(
        gm.program(m, p, batch, None), p, batch, ref.config_of(gm.CONFIG))
    assert loss <= 5e-3 and mtp <= 1e-2, (loss, mtp)
    assert logits <= 0.15 and mtp_logits <= 0.15, (logits, mtp_logits)
    assert grads <= 1.0 and margin <= 0.05, (grads, margin)


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test (model-configs, section 4): the 8 shares of 2
    experts each of a 16-expert layer, routed by the one selection, add up
    with the shared expert ONCE to the uncut reference's layer (every expert
    held, the shared one inside)."""
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    d, f, total, held, k, n = 32, 16, 16, 2, 4, 64
    u = jax.random.normal(keys[0], (n, d))
    w = {"router": jax.random.normal(keys[1], (d, total)),
         "expert_bias": 0.05 * jax.random.normal(keys[2], (total,)),
         "w13": 0.2 * jax.random.normal(keys[3], (total, d, 2 * f)),
         "w2": 0.2 * jax.random.normal(keys[4], (total, f, d)),
         "shared_in": 0.2 * jax.random.normal(keys[5], (d, 2 * f)),
         "shared_out": 0.2 * jax.random.normal(keys[6], (f, d))}
    cfg = {"num_experts_per_tok": k, "routed_scaling_factor": 1.8,
           "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        uncut, free, _, _ = ref._experts(w, u[None], cfg, None, False)
        shared = ref._swiglu(u, w["shared_in"], w["shared_out"])
        parts, chosen = [], []
        for first in range(0, total, held):
            out, report = experts_op.routed_experts(
                u, w["router"], w["expert_bias"],
                w["w13"][first:first + held], w["w2"][first:first + held],
                first=first, top_k=k, scaling=1.8, weight_eps=1e-20)
            assert float(report["dropped"]) == 0
            parts.append(out)
            chosen.append(np.asarray(report["sel"]))
    assert all((c == chosen[0]).all() for c in chosen)
    assert (np.sort(chosen[0], -1) == np.sort(np.asarray(free[0]), -1)).all()
    total_out = sum(parts) + shared
    assert float(jnp.abs(total_out - uncut[0]).max()) <= 1e-5 * float(
        jnp.abs(uncut).max())
    # the shared expert a share, or left out, is not the layer
    assert float(jnp.abs(sum(parts) + 8 * shared - uncut[0]).max()) > 1e-2
    assert float(jnp.abs(sum(parts) - uncut[0]).max()) > 1e-2
