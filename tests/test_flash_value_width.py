"""``flash_attention`` with v, o and do at a width of their own (latent
attention's training side: keys of 192 over values of 128), in interpret
mode at small widths: forward and both backward forms against the exact
reference, and what the shape rules say of two widths."""

import importlib

import jax
import jax.numpy as jnp
import pytest
from ling_hybrid_model import no_persistent_cache  # noqa: F401 - autouse

fa = importlib.import_module("raydp_tpu.ops.flash_attention")

B, H, T, D, DV, BLOCK = 1, 2, 128, 48, 32, 32


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    return (jax.random.normal(keys[0], (B, H, T, D)),
            jax.random.normal(keys[1], (B, H, T, D)),
            jax.random.normal(keys[2], (B, H, T, DV)),
            jax.random.normal(keys[3], (B, H, T, DV)))


@pytest.fixture(scope="module")
def want(operands):
    q, k, v, weight = operands
    with jax.default_matmul_precision("highest"):
        return fa._reference(q, k, v, True), jax.grad(
            lambda q, k, v: (fa._reference(q, k, v, True) * weight).sum(),
            argnums=(0, 1, 2))(q, k, v)


def close(got, want, limit=5e-6):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max()) <= limit


def test_forward_at_a_value_width_of_its_own(operands, want):
    q, k, v, _ = operands
    with jax.default_matmul_precision("highest"):
        o = fa.flash_attention(q, k, v, True, BLOCK, BLOCK)
    assert o.shape == (B, H, T, DV) and close(o, want[0])


@pytest.mark.parametrize("window", [None, 40])
def test_the_fused_backward_at_two_widths(operands, want, window):
    q, k, v, weight = operands
    assert fa.backward_form(T, T, D, 4, block_q=BLOCK, block_k=BLOCK,
                            value_dim=DV) == "fused"
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda q, k, v: (fa.flash_attention(
            q, k, v, True, BLOCK, BLOCK, None, window) * weight).sum(),
            argnums=(0, 1, 2))(q, k, v)
        if window is not None:
            from raydp_tpu.parallel.ring_attention import full_attention
            ref = jax.grad(lambda q, k, v: (full_attention(
                q, k, v, causal=True, window=window) * weight).sum(),
                argnums=(0, 1, 2))(q, k, v)
        else:
            ref = want[1]
    assert [g.shape[-1] for g in got] == [D, D, DV]
    assert all(close(a, b) for a, b in zip(got, ref))


def test_the_two_call_backward_at_two_widths_has_the_fused_calls_bits(
        operands, want):
    """Traced offsets are the two-call pass (``backward_form``); its dq, dk
    and dv are the fused call's, bit for bit."""
    q, k, v, weight = operands

    def backward(q, k, v, zero):
        with jax.default_matmul_precision("highest"):
            o, m, l = fa._flash_call(  # noqa: E741
                q, k, v, 0, 0, True, BLOCK, BLOCK, None, True)
            lse = m + jnp.log(l)
            dsum = (weight * o).sum(axis=-1)
            return fa.flash_backward_blocks(
                q, k, v, lse, dsum, weight, zero, zero, True, BLOCK, BLOCK)

    assert fa.backward_form(T, T, D, 4, block_q=BLOCK, block_k=BLOCK,
                            q_offset=jnp.int32(0), value_dim=DV) == "two_call"
    two = jax.jit(backward)(q, k, v, jnp.int32(0))
    one = jax.jit(lambda q, k, v: backward(q, k, v, 0))(q, k, v)
    assert all(close(a, b) for a, b in zip(two, want[1]))
    assert all(bool((a == b).all()) for a, b in zip(two, one))


def test_the_non_causal_call_at_two_widths(operands):
    q, k, v, weight = operands
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda q, k, v: (fa.flash_attention(
            q, k, v, False, BLOCK, BLOCK) * weight).sum(),
            argnums=(0, 1, 2))(q, k, v)
        ref = jax.grad(lambda q, k, v: (fa._reference(
            q, k, v, False) * weight).sum(), argnums=(0, 1, 2))(q, k, v)
    assert all(close(a, b) for a, b in zip(got, ref))


def test_the_shape_rules_reckon_with_both_widths():
    # equal widths: what they said before there were two
    for head in (64, 128, 256):
        for itemsize in (2, 4):
            assert fa.pick_blocks(8192, 8192, head_dim=head, itemsize=itemsize,
                                  value_dim=head) == fa.pick_blocks(
                8192, 8192, head_dim=head, itemsize=itemsize)
            assert fa.fused_vmem_bytes(8192, head, 512, itemsize, head) == (
                fa.fused_vmem_bytes(8192, head, 512, itemsize))
    assert fa.pick_blocks(8192, 8192, head_dim=128) == (1024, 1024)
    # keys of 192 (256 lanes) over values of 128: the tile halves once
    assert fa.pick_blocks(8192, 8192, head_dim=192, value_dim=128) == (512, 512)
    assert fa.pick_blocks(8192, 8192, head_dim=192, itemsize=4,
                          value_dim=128) == (256, 256)
    narrow = fa.fused_vmem_bytes(8192, 192, 512, 2, 128)
    assert fa.dq_resident_bytes(8192, 192) < narrow < fa.fused_vmem_bytes(
        8192, 192, 512, 2)
    assert fa.backward_form(8192, 8192, 192, 2, value_dim=128) == "fused"


@pytest.mark.parametrize("blocks, block_q, block_k, dtype", [
    (1, 16, 16, jnp.bfloat16), (2, 16, 16, jnp.float32),
    (4, 32, 16, jnp.bfloat16), (8, 16, 32, jnp.float32),
    (16, 16, 16, jnp.bfloat16), (16, 16, 32, jnp.float32)])
def test_the_live_grid_gives_the_rectangular_grids_bits_at_192_over_128(
        blocks, block_q, block_k, dtype):
    """ISSUE 54 at the Ling cell's widths (keys of 192 over values of 128):
    the causal calls step over the tiles under the diagonal alone, and give
    the rectangular grid's bits (the cover of the cases:
    ``test_flash_attention.py``)."""
    from flash_grid_cases import live_grid_against_rectangular

    live_grid_against_rectangular(blocks, block_q, block_k, 192, 128, dtype)
