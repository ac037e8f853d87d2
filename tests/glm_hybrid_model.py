"""What ``test_glm_hybridlm.py`` and ``test_glm_mutations.py`` share: the
``glm4_moe_lite`` family's configuration at its rehearsal sizes, seeded
parameters with every gain moved off 1, and the comparison with the
benchmark's plain reference under the program's routing: the loss with both
terms, the main AND the module's logits, every leaf's gradient, the
selection."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import cells as _cells
from benchmark.reference import glm_moe_lite as ref
from ling_hybrid_model import no_persistent_cache  # noqa: F401 - autouse
from raydp_tpu.models import HybridLM

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 32
NORMS = ("norm1", "norm2", "kv_norm", "q_norm", "final_norm", "enorm", "hnorm")


def config(**changed):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "glm-4.7-flash.json")) as f:
        return {**_cells.sized(json.load(f), True), **changed}


CONFIG = config()
# the dense layer, one expert layer and the module: every mechanism
SHORT = config(num_hidden_layers=2)
V = CONFIG["vocab_size"]


def model(cls=HybridLM, config=CONFIG, **kw):
    return cls.from_config(config, **{
        "dtype": jnp.float32, "loss_chunk": 16, "attn_impl": "full",
        "expert_bias_spread": 0.05,
        "mtp_weight": config["model"]["kwargs"]["mtp_weight"], **kw})


def batch():
    return jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0, V)


def params(m, batch):
    """Seeded parameters with every norm gain moved off 1 and the routers'
    scores spread, so that dropping or misplacing one shows."""
    p = m.init(jax.random.PRNGKey(0), batch, None, method="loss")
    flat = jax.tree_util.tree_leaves_with_path(p)
    keys = jax.random.split(jax.random.PRNGKey(2), len(flat))

    def moved(path, leaf, key):
        name = path[-1].key
        if name in NORMS:
            return leaf + 0.1 * jax.random.normal(key, leaf.shape)
        if name in ("router", "eh_proj"):
            return 10.0 * leaf
        return leaf

    return jax.tree.unflatten(jax.tree.structure(p), [
        moved(path, leaf, k) for (path, leaf), k in zip(flat, keys)])


def program(m, p, x, precision="highest"):
    """(loss, the module's loss, routing, gradients, logits, the module's
    logits) of the program."""
    @jax.jit
    def run(p, x):
        with jax.default_matmul_precision(precision):
            (loss, aux), grads = jax.value_and_grad(
                lambda q: m.apply(q, x, None, True, method="loss"),
                has_aux=True)(p)
            return (loss, aux["mtp_loss"], aux["routing"], grads,
                    m.apply(p, aux["hidden"], method="head"),
                    m.apply(p, aux["mtp_hidden"], method="head"))

    return run(p, x)


def gaps(got, p, x, cfg, reference=ref):
    """What ``program`` gave against the reference UNDER THE PROGRAM'S
    ROUTING: (loss gap, the module's loss gap, main logits gap and the
    module's logits gap relative to max |reference| (the module's last row,
    which has no target and weighs 0, among them: it is computed all the
    same), the worst leaf's gradient gap in L2 relative to the reference's,
    the share of (token, layer) choices that differ from the reference's free
    choice, the reference's largest margin among them)."""
    loss, mtp_loss, routing, grads, logits, mtp_logits = got
    want_loss, aux, want_grads = reference.loss_and_grads(
        p, x, cfg, with_states=True, routing=routing)
    want = reference.logits_of(p, aux["hidden"], cfg)
    want_mtp = reference.logits_of(p, aux["mtp_hidden"], cfg)
    worst = max(
        float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-20))
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)))
    differ = (np.sort(np.asarray(routing), axis=-1)
              != np.sort(np.asarray(aux["selection"]), axis=-1)).any(axis=-1)
    margins = np.asarray(aux["margin"])[differ]
    return (abs(float(loss) - float(want_loss)),
            abs(float(mtp_loss) - float(aux["mtp_loss"])),
            float(jnp.abs(logits - want).max() / jnp.abs(want).max()),
            float(jnp.abs(mtp_logits - want_mtp).max()
                  / jnp.abs(want_mtp).max()), worst,
            float(differ.mean()), float(margins.max()) if margins.size else 0.0)


# float32 / highest on both sides: loss, the module's loss, logits, the
# module's logits, gradients, the share of choices that differ, the margin
# among them
MATCHED = (1e-5, 1e-5, 2e-5, 2e-5, 1e-4, 1e-4, 1e-5)
