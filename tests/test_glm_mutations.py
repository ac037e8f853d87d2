"""One-line mutations of the reference's equations
(``benchmark/reference/glm_moe_lite``) that the comparison with the program
must catch: each moves one of the matched readings (the loss, the module's
loss, either logits, a gradient, the selection) past its limit. The dense
layer, one expert layer and the module (``glm_hybrid_model.SHORT``: every
mechanism); the program runs once."""

import inspect
import types

import pytest

import glm_hybrid_model as gm
from glm_hybrid_model import no_persistent_cache  # noqa: F401 - autouse
from glm_hybrid_model import MATCHED
from benchmark.reference import glm_moe_lite as ref

CFG = ref.config_of(gm.SHORT)


@pytest.fixture(scope="module")
def ran():
    m, batch = gm.model(config=gm.SHORT), gm.batch()
    p = gm.params(m, batch)
    return gm.program(m, p, batch), p, batch


def test_the_true_reference_passes(ran):
    got = gm.gaps(*ran, CFG)
    assert all(g <= limit for g, limit in zip(got, MATCHED)), got


def _mutant(*changes):
    """The reference with one-line changes to its equations' text."""
    source = inspect.getsource(ref)
    for old, new in changes:
        assert source.count(old) == 1, old
        source = source.replace(old, new)
    module = types.ModuleType("glm_moe_lite_mutant")
    module.__dict__["__name__"] = "benchmark.reference.glm_moe_lite_mutant"
    exec(compile(source, "<mutant of glm_moe_lite>", "exec"), module.__dict__)
    return module


MUTATIONS = {
    "the_module_fed_the_normed_stream": [(
        "    stream = h_last\n",
        '    stream = _rms(h_last, p["final_norm"], eps)\n')],
    "targets_shifted_by_1_for_2": [(
        "        targets = jnp.pad(x[:, 2:], ((0, 0), (0, 1)))\n",
        "        targets = x[:, 1:]\n")],
    "lambda_dropped": [(
        'return main + cfg["mtp_weight"] * aux["mtp_loss"], aux',
        'return main + aux["mtp_loss"], aux')],
    "the_modules_loss_left_out": [(
        'return main + cfg["mtp_weight"] * aux["mtp_loss"], aux',
        "return main, aux")],
    "the_last_row_counted": [(
        'aux["mtp_loss"] = jnp.mean(ce[:, :-1]).astype(jnp.float32)',
        'aux["mtp_loss"] = jnp.mean(ce).astype(jnp.float32)')],
    "the_hidden_state_first_in_eh_proj": [(
        '    return jnp.concatenate([_rms(p["embed"][ahead], w["enorm"], eps),\n'
        '                            _rms(stream, w["hnorm"], eps)], axis=-1)',
        '    return jnp.concatenate([_rms(stream, w["hnorm"], eps),\n'
        '                            _rms(p["embed"][ahead], w["enorm"], eps)], axis=-1)')],
    "the_modules_own_final_norm_for_the_main_one": [(
        'g = _rms(g, p["mtp_0"]["final_norm"], cfg["rms_norm_eps"])',
        'g = _rms(g, p["final_norm"], cfg["rms_norm_eps"])')],
    "the_query_norm_left_out": [(
        'c_q = _rms(x @ w["wqa"], w["q_norm"], eps)',
        'c_q = x @ w["wqa"]')],
    "k_r_not_shared": [(
        '+ jnp.einsum("bqd,bkd->bqk", q_p, k_r)) * scale',
        '+ jnp.einsum("bqd,bkd->bqk", q_p, '
        "_rope_pairs(k_nope[..., :rope], theta))) * scale")],
    "rope_on_halves_for_pairs": [(
        "even, odd = x[..., 0::2], x[..., 1::2]",
        "even, odd = x[..., :d // 2], x[..., d // 2:]")],
    "scaled_by_the_nope_width_alone": [(
        "scale = jnp.asarray((nope + rope) ** -0.5, x.dtype)",
        "scale = jnp.asarray(nope ** -0.5, x.dtype)")],
    "weights_not_normalised": [(
        "    weight = picked / (jnp.sum(picked, axis=-1, keepdims=True)\n"
        "                       + jnp.asarray(WEIGHT_EPS, u.dtype))\n",
        "    weight = picked\n")],
    "the_scaling_of_1.8_left_out": [(
        '    weight = weight * jnp.asarray(cfg["routed_scaling_factor"], '
        "u.dtype)\n", "")],
    "the_shared_expert_added_twice": [(
        'out = out + _swiglu(u, w["shared_in"], w["shared_out"])',
        'out = out + 2 * _swiglu(u, w["shared_in"], w["shared_out"])')],
    "bias_in_the_weights": [(
        "picked = jnp.take_along_axis(scores, sel, axis=-1)",
        'picked = jnp.take_along_axis(scores + w["expert_bias"].astype('
        "u.dtype), sel, axis=-1)")],
    "selected_without_the_bias": [(
        'top, free = jax.lax.top_k(scores + w["expert_bias"].astype(u.dtype), '
        "k + 1)", "top, free = jax.lax.top_k(scores, k + 1)")],
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_a_mutation_fails_the_comparison(ran, mutation):
    """Each moves one of the matched readings to ten times its limit and
    more."""
    got = gm.gaps(*ran, CFG, _mutant(*MUTATIONS[mutation]))
    assert max(g / limit for g, limit in zip(got, MATCHED)) >= 10, (
        mutation, got)
