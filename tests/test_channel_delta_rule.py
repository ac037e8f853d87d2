"""``ops.delta_rule.channel_gated_delta_rule`` (a decay a CHANNEL of the key:
Kimi Delta Attention's rule) against the recurrence token by token, forward
and gradients: chunks of 16 and 64 tokens (one sub-block a chunk, and four,
whose products go through a sub-block's first row), log-decays at the bound
(-5 every token and channel: -320 over a chunk, where a factorised form
overflows), no decay, heads in groups."""

import jax
import jax.numpy as jnp
import pytest
from ling_hybrid_model import no_persistent_cache  # noqa: F401 - autouse

from raydp_tpu.ops import delta_rule

B, T, H, DK, DV = 2, 128, 4, 16, 24


def recurrence(q, k, v, log_alpha, beta):
    """Decay a column, erase, write, read: a token at a time."""
    def token(state, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        state = state * jnp.exp(a_t)[:, :, None, :]
        held = jnp.einsum("bhvd,bhd->bhv", state, k_t)
        state = state + (b_t[..., None] * (v_t - held))[..., None] * (
            k_t[:, :, None, :])
        return state, jnp.einsum("bhvd,bhd->bhv", state, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros(q.shape[:1] + (q.shape[2], v.shape[-1], q.shape[-1])),
        tuple(z.swapaxes(0, 1) for z in (q, k, v, log_alpha, beta)))
    return o.swapaxes(0, 1)


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return {
        "q": unit(jax.random.normal(keys[0], (B, T, H, DK))) * DK ** -0.5,
        "k": unit(jax.random.normal(keys[1], (B, T, H, DK))),
        "v": jax.random.normal(keys[2], (B, T, H, DV)),
        "beta": jax.nn.sigmoid(jax.random.normal(keys[3], (B, T, H))),
        "weight": jax.random.normal(keys[4], (B, T, H, DV)),
        "decays": {
            "mixed": -5.0 * jax.nn.sigmoid(
                3.0 * jax.random.normal(keys[5], (B, T, H, DK))),
            "at_the_bound": jnp.full((B, T, H, DK), -5.0),
            "none": jnp.zeros((B, T, H, DK))}}


def both(rule, o, log_alpha):
    def loss(q, k, v, a, b):
        return (rule(q, k, v, a, b) * o["weight"]).sum()

    args = (o["q"], o["k"], o["v"], log_alpha, o["beta"])
    with jax.default_matmul_precision("highest"):
        return rule(*args), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


@pytest.mark.parametrize("decay, chunk, heads_at_once", [
    ("mixed", 16, 8), ("mixed", 64, 8), ("mixed", 64, 2),
    ("at_the_bound", 16, 8), ("at_the_bound", 64, 2), ("none", 64, 8)])
def test_the_chunked_form_is_the_recurrence(operands, decay, chunk,
                                            heads_at_once):
    log_alpha = operands["decays"][decay]
    want_o, want_g = both(recurrence, operands, log_alpha)
    got_o, got_g = both(
        lambda *a: delta_rule.channel_gated_delta_rule(
            *a, chunk=chunk, heads_at_once=heads_at_once), operands, log_alpha)
    assert bool(jnp.isfinite(got_o).all())
    assert float(jnp.abs(got_o - want_o).max() / jnp.abs(want_o).max()) < 1e-5
    for name, got, want in zip("q k v log_alpha beta".split(), got_g, want_g):
        assert bool(jnp.isfinite(got).all()), name
        gap = float(jnp.abs(got - want).max() / (jnp.abs(want).max() + 1e-30))
        assert gap < 2e-4, (name, gap)


def test_a_decay_alike_on_every_channel_is_the_rule_with_a_decay_a_head(
        operands):
    """Where a head's channels all decay alike, the rule IS
    ``gated_delta_rule``'s (another algebra: the decay outside the chunk's
    contraction)."""
    log_alpha = -jax.nn.softplus(jax.random.normal(
        jax.random.PRNGKey(7), (B, T, H)))
    o = operands
    with jax.default_matmul_precision("highest"):
        a_head = delta_rule.gated_delta_rule(
            o["q"], o["k"], o["v"], log_alpha, o["beta"])
        a_channel = delta_rule.channel_gated_delta_rule(
            o["q"], o["k"], o["v"],
            jnp.broadcast_to(log_alpha[..., None], (B, T, H, DK)), o["beta"])
    assert float(jnp.abs(a_head - a_channel).max()) < 1e-5


def test_bf16_operands_keep_float32_decays_and_state(operands):
    o = operands
    log_alpha = o["decays"]["mixed"]
    want = recurrence(o["q"], o["k"], o["v"], log_alpha, o["beta"])
    got = delta_rule.channel_gated_delta_rule(
        *(o[n].astype(jnp.bfloat16) for n in "qkv"), log_alpha, o["beta"])
    assert got.dtype == jnp.bfloat16
    assert float(jnp.abs(got.astype(jnp.float32) - want).max()
                 / jnp.abs(want).max()) < 3e-2


def test_lengths_that_do_not_divide_are_refused(operands):
    o = operands
    with pytest.raises(ValueError, match="does not divide"):
        delta_rule.channel_gated_delta_rule(
            o["q"], o["k"], o["v"], o["decays"]["none"], o["beta"], chunk=48)
    with pytest.raises(ValueError, match="groups of"):
        delta_rule.channel_gated_delta_rule(
            o["q"], o["k"], o["v"], o["decays"]["none"], o["beta"],
            heads_at_once=3)


def test_the_scan_lies_under_its_scope_and_names_its_result():
    from raydp_tpu.obs import profiler

    def f(q, k, v, a, b):
        return delta_rule.channel_gated_delta_rule(q, k, v, a, b)

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, 64, 2, 8), (1, 64, 2, 8), (1, 64, 2, 8), (1, 64, 2, 8), (1, 64, 2))]
    text = jax.jit(f).lower(*shapes).compile().as_text()
    chains = [tuple(said["scopes"])
              for said in profiler.scopes_in_text(text).values()]
    assert any(delta_rule.SCOPE in c for c in chains)
    assert delta_rule.SAVED_OUTPUT in str(jax.make_jaxpr(f)(*shapes))
