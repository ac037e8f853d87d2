"""The chunked gated delta rule (ops/delta_rule.py) against the per-token
recurrence written out here, float32 on the CPU, seeded: in value and in
every argument's gradient, for chunk lengths 4, 8, 16 and the whole sequence,
with ``beta`` up to 2 (a step that reflects: negative eigenvalues), with
decays from Mamba-2's published initialisation, and with a chunk whose summed
log-decay is under -100 (where a quotient of cumulative products is 0 / 0)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.ops import delta_rule
from raydp_tpu.ops.delta_rule import SAVED_OUTPUT, SCOPE, gated_delta_rule

B, T, H, DK, DV = 2, 32, 3, 8, 16
NAMES = ("q", "k", "v", "log_alpha", "beta")


def recurrence(q, k, v, log_alpha, beta, erase_first=True):
    """S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T;
    o_t = S_t q_t: decay, erase, write, read, token by token."""
    def token(state, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        state = jnp.exp(a_t)[..., None, None] * state
        write = (b_t[..., None] * v_t)[..., None] * k_t[:, :, None, :]
        if not erase_first:  # the mutation: the erase sees the new write
            state = state + write
        held = jnp.einsum("bhvd,bhd->bhv", state, k_t)
        state = state - (b_t[..., None] * held)[..., None] * k_t[:, :, None, :]
        if erase_first:
            state = state + write
        return state, jnp.einsum("bhvd,bhd->bhv", state, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((q.shape[0], H, DV, DK), jnp.float32),
        tuple(z.swapaxes(0, 1) for z in (q, k, v, log_alpha, beta)))
    return o.swapaxes(0, 1)


def inputs(seed=0, decay_scale=1.0, beta_spread=2.0):
    """q and k l2-normed a head (q scaled by Dk ** -0.5) as the model hands
    them over; the decay as the published initialisation gives it (a step
    log-uniform in [0.001, 0.1] a head, moved a little a token, times A =
    U(1, 16)); beta = 2 sigmoid(.) spread over (0, 2)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)

    def l2(x):
        return x / jnp.linalg.norm(x, axis=-1, keepdims=True)

    dt_head = jnp.exp(jax.random.uniform(
        keys[0], (H,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    dt = decay_scale * dt_head * jnp.exp(
        0.3 * jax.random.normal(keys[1], (B, T, H)))
    a = jax.random.uniform(keys[2], (H,), jnp.float32, 1.0, 16.0)
    return (l2(jax.random.normal(keys[3], (B, T, H, DK))) * DK ** -0.5,
            l2(jax.random.normal(keys[4], (B, T, H, DK))),
            jax.random.normal(keys[5], (B, T, H, DV)),
            -a * dt,
            2.0 * jax.nn.sigmoid(
                beta_spread * jax.random.normal(keys[6], (B, T, H))))


def value_and_grads(fn, args):
    """A scalar that weighs every output differently, and its gradient in
    every argument."""
    weights = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, DV))
    with jax.default_matmul_precision("highest"):
        o = fn(*args)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * weights),
                         argnums=tuple(range(len(args))))(*args)
    return o, grads


def gap(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("chunk", [4, 8, 16, T])
def test_chunked_form_is_the_recurrence_in_value_and_gradient(chunk):
    args = inputs()
    assert float(args[4].max()) > 1.95 and float(args[4].min()) < 0.05
    o, grads = value_and_grads(
        lambda *a: gated_delta_rule(*a, chunk=chunk), args)
    o_ref, grads_ref = value_and_grads(recurrence, args)
    assert o.shape == (B, T, H, DV) and o.dtype == jnp.float32
    assert gap(o, o_ref) <= 1e-5
    for name, got, want in zip(NAMES, grads, grads_ref):
        assert gap(got, want) <= 1e-5, name


@pytest.mark.parametrize("chunk", [8, T])
def test_a_chunk_that_decays_past_float32_stays_finite(chunk):
    """The decay 200 times the published one: a chunk's summed log-decay is
    under -100 (and the sequence's under -1000), ``exp`` of the running sum
    is 0 in float32 and a quotient of cumulative products 0 / 0. Built from
    DIFFERENCES of the running sums, value and gradients are finite and
    still the recurrence's."""
    args = inputs(seed=1, decay_scale=200.0)
    per_chunk = args[3].reshape(B, T // chunk, chunk, H).sum(2)
    assert float(per_chunk.min()) < -100.0
    o, grads = value_and_grads(
        lambda *a: gated_delta_rule(*a, chunk=chunk), args)
    o_ref, grads_ref = value_and_grads(recurrence, args)
    assert bool(jnp.isfinite(o).all())
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    assert gap(o, o_ref) <= 1e-5
    for name, got, want in zip(NAMES, grads, grads_ref):
        assert gap(got, want) <= 1e-4, name


@pytest.mark.parametrize("beta", [0.0, 1.0, 2.0])
def test_the_steps_ends_are_what_the_rule_says(beta):
    """beta 0 writes nothing (o is 0); beta 1 with no decay REPLACES what
    the state holds for a key (reading a key back right away gives its
    value); beta 2 REFLECTS (reading it back gives 2 v - what was held)."""
    q, k, v, log_alpha, _ = inputs(seed=2)
    k = jnp.broadcast_to(k[:, :1], k.shape)  # one key a head, every token
    flat = jnp.full((B, T, H), beta, jnp.float32)
    with jax.default_matmul_precision("highest"):
        o = gated_delta_rule(k, k, v, jnp.zeros_like(log_alpha), flat, chunk=8)
    # with one unit key, S_t k = (1 - beta) S_{t-1} k + beta v_t
    want, held = [], jnp.zeros((B, H, DV))
    for t in range(T):
        held = (1.0 - beta) * held + beta * v[:, t]
        want.append(held)
    want = jnp.stack(want, axis=1)  # all zeros at beta 0; grows at beta 2
    assert float(jnp.abs(o - want).max()) <= 1e-5 * max(
        1.0, float(jnp.abs(want).max()))


def test_the_erase_comes_before_the_write():
    """The recurrence with the erase applied AFTER the write is another
    function: the chunked form is the first."""
    args = inputs()
    with jax.default_matmul_precision("highest"):
        o = gated_delta_rule(*args, chunk=8)
        other = recurrence(*args, erase_first=False)
    assert gap(o, recurrence(*args)) <= 1e-5 < 1e-2 <= gap(o, other)


def test_a_chunk_must_divide_the_sequence_and_a_short_one_is_one_chunk():
    args = inputs()
    with pytest.raises(ValueError, match="does not divide"):
        gated_delta_rule(*args, chunk=5)
    with jax.default_matmul_precision("highest"):
        assert gap(gated_delta_rule(*args, chunk=256),
                   gated_delta_rule(*args, chunk=T)) == 0.0
    assert delta_rule.CHUNK == 64


def test_operands_take_the_compute_dtype_and_the_output_carries_its_name():
    """bf16 in, bf16 out, close to the float32 form (decays and beta stay
    float32); the result is named for a save-by-name checkpoint policy and
    the region for a trace reader."""
    args = inputs()
    low = gated_delta_rule(*(a.astype(jnp.bfloat16) if i < 3 else a
                             for i, a in enumerate(args)), chunk=8)
    assert low.dtype == jnp.bfloat16
    assert gap(low.astype(jnp.float32),
               gated_delta_rule(*args, chunk=8)) < 0.03
    jaxpr = str(jax.make_jaxpr(
        lambda *a: gated_delta_rule(*a, chunk=8))(*args))
    assert f"name={SAVED_OUTPUT}" in jaxpr
    assert "triangular_solve" in jaxpr and jaxpr.count("triangular_solve") == 1
    lowered = jax.jit(lambda *a: gated_delta_rule(*a, chunk=8)).lower(*args)
    assert f"{SCOPE}/" in lowered.as_text(debug_info=True)


def test_the_recurrences_flops_are_six_products_of_the_state():
    assert delta_rule.recurrence_flops(8192, 15, 96, 192) == (
        6 * 96 * 192 * 15 * 8192)
