"""The plain reference against the program, tiny sizes, on the CPU."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from benchmark.reference import dlrm as ref_dlrm  # noqa: E402


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_dlrm_reference_matches_the_programs_loss_and_gradients(seed):
    from raydp_tpu.estimator.jax_estimator import _LOSSES
    from raydp_tpu.models import DLRM

    vocab = (50, 7, 300, 3)
    model = DLRM(vocab_sizes=vocab, num_dense=4, embed_dim=8,
                 bottom_mlp=(16, 8), top_mlp=(16, 8),
                 use_pallas_interaction=True)
    rng = np.random.default_rng(seed)
    dense = jnp.asarray(rng.standard_normal((32, 4)), jnp.float32)
    ids = jnp.asarray(np.stack([rng.integers(0, v, 32) for v in vocab], 1),
                      jnp.int32)
    y = jnp.asarray(rng.integers(0, 2, 32), jnp.float32)
    params = model.init(jax.random.PRNGKey(seed), (dense, ids))
    loss, grads = jax.value_and_grad(
        lambda p: _LOSSES["bce"](model.apply(p, (dense, ids)), y))(params)
    want, logits, want_grads = ref_dlrm.loss_and_grads(params, dense, ids, y, 2, 2)
    assert float(loss) == pytest.approx(float(want), abs=1e-6)
    np.testing.assert_allclose(model.apply(params, (dense, ids)), logits, atol=1e-5)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(got, ref, atol=1e-6)


def test_dlrm_reference_loss_is_smooth_where_every_logit_is_zero():
    """A dead network's logits are exactly 0; the reference's gradient there
    must be sigmoid(0) - y like the program's (a max/abs spelling is not)."""
    x = jnp.zeros((4, 1))
    y = jnp.asarray([0.0, 1.0, 1.0, 0.0])
    grad = jax.grad(lambda x: ref_dlrm.bce_with_logits(x, y))(x)
    np.testing.assert_allclose(grad[:, 0], (0.5 - np.asarray(y)) / 4)
