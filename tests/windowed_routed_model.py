"""What the two files of the ``smallthinker`` family's tests share
(``test_windowed_routed_hybridlm.py``: the layer, the shares, ``from_config``,
placement, the fit; ``test_windowed_routed_reference.py``: the model against
the plain reference and its mutations): the catalog row's keys at tiny widths,
the model built from them, a batch, seeded parameters, and the comparison. A
test file imports the fixtures it uses by name."""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import smallthinker as ref  # noqa: E402
from raydp_tpu.models import RoutedHybridLM  # noqa: E402

V, T, W = 256, 40, 8
LAYOUT = [0, 1, 1, 1] * 3
# the catalog row's keys at tiny widths: published layers 4-7 (the second
# period), experts 4-7 of 16
CONFIG = {
    "model_name": "smallthinker_tiny", "model_type": "smallthinker",
    "head_dim": 16, "hidden_size": 48,
    "max_position_embeddings": 64, "moe_ffn_hidden_size": 24,
    "moe_num_active_primary_experts": 3, "moe_num_primary_experts": 4,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_hidden_layers": 4,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_layout": LAYOUT,
    "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_layout": LAYOUT, "sliding_window_size": W,
    "tie_word_embeddings": False, "vocab_size": V,
    "share": {"first_layer": 4, "experts_total": 16, "first_expert": 4}}
CFG = ref.config_of(CONFIG)


def model(config=CONFIG, **kw):
    return RoutedHybridLM.from_config(
        config, **{"dtype": jnp.float32, "loss_chunk": 16, **kw})


@pytest.fixture(scope="module")
def batch():
    return jax.random.randint(jax.random.PRNGKey(0), (2, T + 1), 0, V)


@pytest.fixture(scope="module")
def params(batch):
    return model().init(jax.random.PRNGKey(1), batch, None, method="loss")


def objective(module, params, batch):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda p: module.apply(p, batch, None, True, method="loss"),
            has_aux=True)(params)


def gaps(got, want):
    """(loss, hidden, worst relative gradient gap, decisions that differ)."""
    (loss, aux), grads = got
    ref_loss, ref_aux, ref_grads = want
    gap = max(float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
              for a, b in zip(jax.tree.leaves(grads),
                              jax.tree.leaves(ref_grads)))
    differ = (np.sort(aux["routing"], -1)
              != np.sort(ref_aux["selection"], -1)).any(-1).sum()
    return (abs(float(loss) - float(ref_loss)),
            float(jnp.abs(aux["hidden"] - ref_aux["hidden"]).max()), gap,
            int(differ))
