"""What ``test_ling_hybridlm.py`` and ``test_ling_mutations.py`` share: the
``bailing_hybrid`` family's configuration at its rehearsal sizes, seeded
parameters moved into every mechanism's live range, and the comparison with
the benchmark's plain reference under the program's routing."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import cells as _cells
from benchmark.reference import ling_hybrid as ref
from raydp_tpu.models import HybridLM



@pytest.fixture(autouse=True, scope="module")
def no_persistent_cache():
    """These files compile dozens of programs of a second and more (the
    reference op by op, a jit a case): kept out of the checkout's
    ``.jax_cache``, which an earlier test of the same worker may have turned
    on and ``tests/test_compile_cache.py`` watches for strangers."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
T = 32


def config(**changed):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash.json")) as f:
        return {**_cells.sized(json.load(f), True), **changed}


CONFIG = config()
# two layers that hold every mechanism: KDA over the dense SwiGLU, latent
# attention over the experts (a group of 2 from published layer 0 on)
SHORT = config(layer_group_size=2, num_hidden_layers=2,
               share={"first_layer": 0, "first_expert": 2, "experts_total": 16})
V = CONFIG["vocab_size"]


def model(cls=HybridLM, config=CONFIG, **kw):
    return cls.from_config(config, **{
        "dtype": jnp.float32, "loss_chunk": 16, "attn_impl": "full",
        "expert_bias_spread": 0.05, **kw})


def batch():
    return jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0, V)


def params(m, batch):
    """Seeded parameters with every norm gain moved off 1 and the KDA gates
    moved into their live range (as seeded, exp(A_log) x dt_bias saturates
    most channels' sigmoid at no decay: a mutation of the decay would not
    show), so that dropping or misplacing one shows."""
    p = m.init(jax.random.PRNGKey(0), batch, None, method="loss")
    flat = jax.tree_util.tree_leaves_with_path(p)
    keys = jax.random.split(jax.random.PRNGKey(2), len(flat))

    def moved(path, leaf, key):
        name = path[-1].key
        noise = jax.random.normal(key, leaf.shape)
        if name in ("gate_norm", "norm1", "norm2", "kv_norm", "final_norm"):
            return leaf + 0.1 * noise
        if name == "A_log":
            return 0.3 * noise
        if name == "dt_bias":
            return noise
        if name in ("wf", "wb", "wg", "router"):
            return 10.0 * leaf
        return leaf

    return jax.tree.unflatten(jax.tree.structure(p), [
        moved(path, leaf, k) for (path, leaf), k in zip(flat, keys)])


def program(m, p, x):
    """(loss, routing, gradients, logits) of the program, float32 /
    highest."""
    @jax.jit
    def run(p, x):
        with jax.default_matmul_precision("highest"):
            (loss, aux), grads = jax.value_and_grad(
                lambda q: m.apply(q, x, None, True, method="loss"),
                has_aux=True)(p)
            return loss, aux["routing"], grads, m.apply(p, x[:, :-1])

    return run(p, x)


def gaps(got, p, x, cfg, reference=ref):
    """What ``program`` gave against the reference UNDER THE PROGRAM'S
    ROUTING (run op by op: at these sizes a compile of the recurrence token
    by token and its gradient takes longer than its run): (loss gap, logits gap relative to
    max |reference|, the worst parameter's gradient gap in L2 relative to
    the reference's, the share of (token, layer) choices that differ from the
    reference's free choice, the reference's largest margin among them)."""
    loss, routing, grads, logits = got
    want_loss, aux, want_grads = reference.loss_and_grads(
        p, x, cfg, with_states=True, routing=routing)
    want_logits = reference.logits_of(p, aux["hidden"], cfg)
    worst = max(
        float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-20))
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)))
    differ = (np.sort(np.asarray(routing), axis=-1)
              != np.sort(np.asarray(aux["selection"]), axis=-1)).any(axis=-1)
    margins = np.asarray(aux["margin"])[differ]
    return (abs(float(loss) - float(want_loss)),
            float(jnp.abs(logits - want_logits).max()
                  / jnp.abs(want_logits).max()), worst,
            float(differ.mean()), float(margins.max()) if margins.size else 0.0)


# the traffic file's ``matched`` limits: loss, logits, gradients, the share
# of choices that differ, the margin among them
MATCHED = (1e-5, 2e-5, 1e-4, 1e-4, 1e-5)
