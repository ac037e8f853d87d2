"""One-line mutations of the reference's equations
(``benchmark/reference/ling_hybrid``) that the comparison with the program
must catch: each moves one of the traffic file's ``matched`` readings (loss,
logits, a gradient, the selection) past its limit. Two layers that hold every
mechanism (``ling_hybrid_model.SHORT``); the program runs once."""

import inspect
import types

import pytest

import ling_hybrid_model as lm
from ling_hybrid_model import no_persistent_cache  # noqa: F401 - autouse
from ling_hybrid_model import MATCHED
from benchmark.reference import ling_hybrid as ref

CFG = ref.config_of(lm.SHORT)


@pytest.fixture(scope="module")
def ran():
    m, batch = lm.model(config=lm.SHORT), lm.batch()
    p = lm.params(m, batch)
    return lm.program(m, p, batch), p, batch


def test_the_true_reference_passes(ran):
    got = lm.gaps(*ran, CFG)
    assert all(g <= limit for g, limit in zip(got, MATCHED)), got


def _mutant(*changes):
    """The reference with one-line changes to its equations' text."""
    source = inspect.getsource(ref)
    for old, new in changes:
        assert source.count(old) == 1, old
        source = source.replace(old, new)
    module = types.ModuleType("ling_hybrid_mutant")
    module.__dict__["__name__"] = "benchmark.reference.ling_hybrid_mutant"
    exec(compile(source, "<mutant of ling_hybrid>", "exec"), module.__dict__)
    return module


DECAY = "        state = state * a_t[:, :, None, :]  # decay: column d by a_t[d]\n"
ERASE = ("        state = state - (b_t[..., None] * held)[..., None] "
         "* k_t[:, :, None, :]\n")
MUTATIONS = {
    "decay_after_the_erase": [(DECAY, ""), (ERASE, ERASE + DECAY)],
    "beta_to_2": [('beta = jax.nn.sigmoid(x @ w["wb"])',
                   'beta = 2.0 * jax.nn.sigmoid(x @ w["wb"])')],
    "a_scalar_decay": [(
        "v.reshape(b, t, heads, -1), alpha, beta, SCAN_BLOCK)",
        "v.reshape(b, t, heads, -1), jnp.broadcast_to("
        "alpha.mean(-1, keepdims=True), alpha.shape), beta, SCAN_BLOCK)")],
    "softplus_gate_for_the_bounded_one": [(
        'jnp.asarray(cfg["kda_lower_bound"], x.dtype)\n'
        '                    * jax.nn.sigmoid(jnp.exp(w["A_log"])[:, None] * gate))',
        '-jnp.exp(w["A_log"])[:, None] * jax.nn.softplus(gate))')],
    "rope_on_the_nope_part": [(
        'jnp.einsum("bqd,bkd->bqk", q_h[..., :nope], up_h[..., :nope])',
        'jnp.einsum("bqd,bkd->bqk", _rope_pairs(q_h[..., :nope], theta), '
        '_rope_pairs(up_h[..., :nope], theta))')],
    "k_rope_a_head": [(
        "_rope_pairs(q_h[..., nope:], theta), k_rope)",
        "_rope_pairs(q_h[..., nope:], theta), "
        "_rope_pairs(up_h[..., :rope], theta))")],
    "rope_on_halves_for_pairs": [(
        "even, odd = x[..., 0::2], x[..., 1::2]",
        "even, odd = x[..., :d // 2], x[..., d // 2:]")],
    "group_limit_dropped": [("    if groups:\n", "    if False:\n")],
    "bias_in_the_weights": [(
        "picked = jnp.take_along_axis(scores, sel, axis=-1)",
        'picked = jnp.take_along_axis(scores + w["expert_bias"].astype('
        "u.dtype), sel, axis=-1)")],
    "shared_expert_routed": [(
        'out = _swiglu(u, w["shared_in"], w["shared_out"])',
        'out = weight[..., :1] * _swiglu(u, w["shared_in"], w["shared_out"])')],
    "head_wise_gate_dropped": [(
        '    o = o * jax.nn.sigmoid(x @ w["wg"])[..., None]\n'
        "    return o.reshape(b, t, heads * dv)",
        "    return o.reshape(b, t, heads * dv)")],
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_a_mutation_fails_the_comparison(ran, mutation):
    """Each moves one of the matched readings to ten times its limit and
    more."""
    got = lm.gaps(*ran, CFG, _mutant(*MUTATIONS[mutation]))
    assert max(g / limit for g, limit in zip(got, MATCHED)) >= 10, (
        mutation, got)
