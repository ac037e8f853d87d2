"""What the three ``test_flash_*`` files share since ISSUE 54: the causal
calls on the LIVE grid (static offsets 0) against the same calls on the
rectangular grid, which the runtime-offset surface keeps (the offsets handed
over as values of the program), bit for bit, and against the exact reference;
and a jaxpr's pallas calls with their grids."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np

fa = importlib.import_module("raydp_tpu.ops.flash_attention")


def pallas_grids(f, *args) -> list:
    """[(the call's name, its grid)] of the pallas calls ``f`` traces to,
    in program order, custom derivatives' bodies included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((eqn.params["name"],
                              tuple(eqn.params["grid_mapping"].grid)))
            for value in eqn.params.values():
                for sub in value if isinstance(value, (list, tuple)) else [
                        value]:
                    if hasattr(sub, "eqns"):
                        walk(sub)
                    elif hasattr(getattr(sub, "jaxpr", None), "eqns"):
                        walk(sub.jaxpr)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return found


def live_grid_against_rectangular(blocks, block_q, block_k, d, dv, dtype,
                                  heads=1):
    """Forward and backward of causal self-attention over ``blocks`` x the
    larger tile: the live grid's o, m, l, dq, dk, dv are the rectangular
    grid's bits (whatever form the backward takes: fused at equal tiles,
    two calls at unequal ones), each call steps over ``causal_steps``' live
    tiles, and in float32 they lie within 1e-4 of the exact reference."""
    t = blocks * max(block_q, block_k)
    keys = jax.random.split(jax.random.PRNGKey(blocks * 131 + d), 4)
    q, k = (jax.random.normal(key, (1, heads, t, d), dtype)
            for key in keys[:2])
    v, g = (jax.random.normal(key, (1, heads, t, dv), dtype)
            for key in keys[2:])
    zero = jnp.int32(0)
    rect_steps, live_steps = fa.causal_steps(t, block_q, block_k)
    assert fa.causal_grid(t, t, block_q, block_k) == "live"
    assert fa.causal_grid(t, t, block_q, block_k, q_offset=zero) == (
        "rectangular:runtime offsets")

    def forward(q_off, k_off):
        return fa._flash_call(q, k, v, q_off, k_off, True, block_q, block_k,
                              None, normalize=True)

    live = forward(0, 0)
    for a, b in zip(live, jax.jit(forward)(zero, zero)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert pallas_grids(lambda: forward(0, 0)) == [
        ("flash_attention_fwd", (heads, live_steps))]
    assert pallas_grids(forward, zero, zero) == [
        ("flash_attention_fwd", (heads, t // block_q, t // block_k))]

    o, m, l = live  # noqa: E741
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    dsum = jnp.sum(g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    def backward(q_off, k_off):
        return fa.flash_backward_blocks(q, k, v, lse, dsum, g, q_off, k_off,
                                        True, block_q, block_k)

    grads = backward(0, 0)
    for a, b in zip(grads, jax.jit(backward)(zero, zero)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    fused = fa.backward_form(t, t, d, q.dtype.itemsize, block_q=block_q,
                             block_k=block_k, value_dim=dv) == "fused"
    assert fused == (block_q == block_k)
    names = ["dq_dkv"] if fused else ["dq", "dkv"]
    assert pallas_grids(lambda: backward(0, 0)) == [
        ("flash_attention_bwd_" + name, (heads, live_steps))
        for name in names]
    assert [grid for _, grid in pallas_grids(backward, zero, zero)] == [
        (heads, t // block_q, t // block_k),
        (heads, t // block_k, t // block_q)]
    assert rect_steps == (t // block_q) * (t // block_k) >= live_steps

    if dtype == jnp.float32:
        want_o, vjp = jax.vjp(lambda *a: fa._reference(*a, True), q, k, v)
        for got, want in zip((o, *grads), (want_o, *vjp(g))):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-4)
