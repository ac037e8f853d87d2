"""The chunked state-space scan (ops/ssd.py) against the per-token recurrence
written out here, float32 on the CPU, seeded: in value and in every
argument's gradient, for chunk sizes 4, 16 and the whole sequence, with
decays from Mamba-2's published initialisation, and with a chunk whose summed
log-decay is under -400."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.ops.ssd import SAVED_OUTPUT, ssd_chunk_scan

B, T, H, P, N = 2, 32, 4, 8, 16


def recurrence(x, dt, A, Bm, Cm, D):
    """S_t = a_t S_{t-1} + dt_t x_t (x) B_t; y_t = S_t C_t + D x_t."""
    def token(state, inputs):
        x_t, dt_t, b_t, c_t = inputs
        a_t = jnp.exp(A * dt_t)
        state = (a_t[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, None, None, :])
        return state, jnp.einsum("bhpn,bn->bhp", state, c_t) + D[:, None] * x_t

    _, y = jax.lax.scan(
        token, jnp.zeros((x.shape[0], H, P, N), jnp.float32),
        (x.swapaxes(0, 1), dt.swapaxes(0, 1), Bm.swapaxes(0, 1),
         Cm.swapaxes(0, 1)))
    return y.swapaxes(0, 1)


def inputs(seed=0, dt_scale=1.0):
    """Decays as the published initialisation gives them: dt log-uniform in
    [0.001, 0.1] a head (moved a little a token, as the projection moves
    it), A = -U(1, 16)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    dt_head = jnp.exp(jax.random.uniform(
        keys[0], (H,), jnp.float32, np.log(1e-3), np.log(1e-1)))
    dt = dt_scale * dt_head * jnp.exp(
        0.3 * jax.random.normal(keys[1], (B, T, H)))
    return (jax.random.normal(keys[2], (B, T, H, P)), dt,
            -jax.random.uniform(keys[3], (H,), jnp.float32, 1.0, 16.0),
            jax.random.normal(keys[4], (B, T, N)),
            jax.random.normal(keys[5], (B, T, N)),
            jax.random.normal(keys[6], (H,)))


def value_and_grads(fn, args):
    """A scalar that weighs every output differently, and its gradient in
    every argument."""
    weights = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, P))
    with jax.default_matmul_precision("highest"):
        y = fn(*args)
        grads = jax.grad(lambda *a: jnp.sum(fn(*a) * weights),
                         argnums=tuple(range(len(args))))(*args)
    return y, grads


def gap(got, want):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max())


@pytest.mark.parametrize("chunk", [4, 16, T])
def test_chunked_scan_is_the_recurrence_in_value_and_gradient(chunk):
    args = inputs()
    y, grads = value_and_grads(
        lambda *a: ssd_chunk_scan(*a, chunk=chunk), args)
    y_ref, grads_ref = value_and_grads(recurrence, args)
    assert y.shape == (B, T, H, P) and y.dtype == jnp.float32
    assert gap(y, y_ref) <= 1e-5
    for name, got, want in zip(("x", "dt", "A", "B", "C", "D"), grads,
                               grads_ref):
        assert gap(got, want) <= 1e-5, name


@pytest.mark.parametrize("chunk", [16, T])
def test_a_chunk_that_decays_past_float32_stays_finite(chunk):
    """dt 150 times the published one: a chunk's summed log-decay is under
    -400, exp of it is 0 in float32, a quotient of cumulative products 0/0.
    Value and gradients are finite and still the recurrence's."""
    args = inputs(seed=1, dt_scale=150.0)
    log_decay = (args[1] * args[2]).reshape(B, T // chunk, chunk, H).sum(2)
    assert float(log_decay.min()) < -400.0
    y, grads = value_and_grads(
        lambda *a: ssd_chunk_scan(*a, chunk=chunk), args)
    y_ref, grads_ref = value_and_grads(recurrence, args)
    assert bool(jnp.isfinite(y).all())
    assert all(bool(jnp.isfinite(g).all()) for g in grads)
    assert gap(y, y_ref) <= 1e-5
    for got, want in zip(grads, grads_ref):
        assert gap(got, want) <= 1e-4


def test_a_chunk_must_divide_the_sequence_and_a_short_one_is_one_chunk():
    args = inputs()
    with pytest.raises(ValueError, match="does not divide"):
        ssd_chunk_scan(*args, chunk=5)
    with jax.default_matmul_precision("highest"):
        assert gap(ssd_chunk_scan(*args, chunk=256),
                   ssd_chunk_scan(*args, chunk=T)) == 0.0


def test_operands_take_the_compute_dtype_and_the_output_carries_its_name():
    """bf16 in, bf16 out, close to the float32 scan; the result is named for
    a save-by-name checkpoint policy and the region for a trace reader."""
    args = inputs()
    low = ssd_chunk_scan(*(a.astype(jnp.bfloat16) if i in (0, 3, 4) else a
                           for i, a in enumerate(args)), chunk=16)
    assert low.dtype == jnp.bfloat16
    assert gap(low.astype(jnp.float32), ssd_chunk_scan(*args, chunk=16)) < 0.03
    jaxpr = str(jax.make_jaxpr(lambda *a: ssd_chunk_scan(*a, chunk=16))(*args))
    assert f"name={SAVED_OUTPUT}" in jaxpr
    lowered = jax.jit(lambda *a: ssd_chunk_scan(*a, chunk=16)).lower(*args)
    assert "ssd/" in lowered.as_text(debug_info=True)
