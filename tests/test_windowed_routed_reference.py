"""The ``smallthinker`` family's model against the plain reference
(benchmark/reference/smallthinker.py) at tiny sizes, float32, seeded random
weights: loss, hidden state, logits, every gradient and the selection decision
for decision, through full attention and the flash kernels, recomputed and
not; the reference under a routing it is given; and every mutation of the
model's options failing the same comparison."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from windowed_routed_model import (  # noqa: F401 - fixtures by name
    CFG, W, batch, gaps, model, objective, params, ref)


@pytest.fixture(scope="module")
def want(params, batch):
    return ref.loss_and_grads(params, batch, CFG, with_states=True)


@pytest.mark.parametrize("attn_impl, remat", [
    ("full", False), ("flash", True), ("flash", False)])
def test_system_against_the_reference(params, batch, want, attn_impl, remat):
    module = model(attn_impl=attn_impl, remat=remat)
    got = objective(module, params, batch)
    loss, hidden, grads, differ = gaps(got, want)
    assert loss <= 2e-6 and hidden <= 2e-5 and grads <= 2e-5, (
        loss, hidden, grads)
    assert differ == 0  # decision for decision
    logits = module.apply(params, got[0][1]["hidden"], method="head")
    np.testing.assert_allclose(
        logits, ref.logits_of(params, want[1]["hidden"], CFG), atol=2e-5)
    np.testing.assert_allclose(
        module.apply(params, batch[:, :-1]),
        ref.forward(params, batch[:, :-1], CFG), atol=2e-5)


def test_the_reference_takes_the_routing_it_is_given(params, batch, want):
    forced = jnp.flip(want[1]["selection"], axis=-1)  # the same sets
    loss, aux, _ = ref.loss_and_grads(params, batch, CFG, routing=forced)
    assert abs(float(loss) - float(want[0])) <= 1e-6
    other = (want[1]["selection"] + 1) % 16
    assert abs(float(ref.loss_and_grads(
        params, batch, CFG, routing=other)[0]) - float(want[0])) > 1e-6
    assert np.array_equal(aux["selection"], want[1]["selection"])
    assert float(aux["margin"].min()) >= 0


MUTATIONS = {
    "no window": dict(attention_windows=()),
    "a window one key narrower": dict(attention_windows=(0, W - 1, W - 1, W - 1)),
    "the global layer windowed": dict(attention_windows=(W,) * 4),
    "RoPE on the global layer": dict(rope_layers=()),
    "no RoPE at all": dict(rope_layers=(0,) * 4),
    "the router fed from the FFN's input": dict(router_input="ffn"),
    "silu for relu": dict(expert_activation="silu"),
    "the next share's experts": dict(first_expert=8),
}


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutation_fails_the_comparison(params, batch, want, mutation):
    module = model(attn_impl="flash").clone(**MUTATIONS[mutation])
    loss, hidden, grads, _ = gaps(objective(module, params, batch), want)
    assert max(loss, hidden, grads) > 1e-3, (loss, hidden, grads)


def test_the_sigmoid_rule_in_this_models_place_fails_the_comparison(
        batch, want):
    module = model(attn_impl="flash").clone(expert_scoring="sigmoid")
    other = module.init(jax.random.PRNGKey(1), batch, None, method="loss")
    assert "expert_bias" in other["params"]["layer_0"]
    (_, aux), _ = objective(module, other, batch)
    assert aux["routing"].shape == want[1]["selection"].shape
