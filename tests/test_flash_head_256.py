"""``flash_attention`` at heads of 256 over values of 256 (latent attention
as ``glm4_moe_lite`` trains it: a shape no cell compiled before ISSUE 53), in
interpret mode at the real widths and a short sequence: forward, the fused
backward and the two-call backward against ``full_attention``, and what the
shape rules say of the width (the compile for a described v5e is
``test_tpu_compile_glm_cell.py``'s)."""

import importlib

import jax
import jax.numpy as jnp
import pytest
from ling_hybrid_model import no_persistent_cache  # noqa: F401 - autouse

from raydp_tpu.parallel.ring_attention import full_attention

fa = importlib.import_module("raydp_tpu.ops.flash_attention")

B, H, T, D, BLOCK = 1, 2, 256, 256, 64


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    return tuple(jax.random.normal(k, (B, H, T, D)) for k in keys)


@pytest.fixture(scope="module")
def want(operands):
    q, k, v, weight = operands
    with jax.default_matmul_precision("highest"):
        return full_attention(q, k, v, causal=True), jax.grad(
            lambda q, k, v: (full_attention(q, k, v, causal=True)
                             * weight).sum(), argnums=(0, 1, 2))(q, k, v)


def close(got, want, limit=5e-6):
    return float(jnp.abs(got - want).max() / jnp.abs(want).max()) <= limit


def test_forward_at_256_over_256(operands, want):
    q, k, v, _ = operands
    with jax.default_matmul_precision("highest"):
        o = fa.flash_attention(q, k, v, True, BLOCK, BLOCK)
    assert o.shape == (B, H, T, D) and close(o, want[0])


def test_the_fused_backward_at_256_over_256(operands, want):
    q, k, v, weight = operands
    assert fa.backward_form(T, T, D, 4, block_q=BLOCK, block_k=BLOCK,
                            value_dim=D) == "fused"
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda q, k, v: (fa.flash_attention(
            q, k, v, True, BLOCK, BLOCK) * weight).sum(),
            argnums=(0, 1, 2))(q, k, v)
    assert all(close(a, b) for a, b in zip(got, want[1]))


def test_the_two_call_backward_at_256_over_256_has_the_fused_calls_bits(
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
                            q_offset=jnp.int32(0), value_dim=D) == "two_call"
    two = jax.jit(backward)(q, k, v, jnp.int32(0))
    one = jax.jit(lambda q, k, v: backward(q, k, v, 0))(q, k, v)
    assert all(close(a, b) for a, b in zip(two, want[1]))
    assert all(bool((a == b).all()) for a, b in zip(two, one))


def test_bf16_operands_at_256_over_256_stay_within_bf16_of_the_reference(
        operands, want):
    """As the cell runs it: bf16 operands, float32 accumulation."""
    q, k, v, weight = (x.astype(jnp.bfloat16) for x in operands)
    got = jax.grad(lambda q, k, v: (fa.flash_attention(
        q, k, v, True, BLOCK, BLOCK).astype(jnp.float32)
        * weight.astype(jnp.float32)).sum(), argnums=(0, 1, 2))(q, k, v)
    assert all(close(a.astype(jnp.float32), b, 0.05)
               for a, b in zip(got, want[1]))


def test_the_shape_rules_at_256_over_256():
    t = 8192
    # a tile's bytes go by the two widths' lanes: 512-row tiles in bf16 (the
    # issue's ``cap * (256 + 256) * 2 <= 1024 * 256 * 2``), 256 in float32
    assert fa.pick_blocks(t, t, head_dim=256, value_dim=256) == (512, 512)
    assert fa.pick_blocks(t, t, head_dim=256, itemsize=4,
                          value_dim=256) == (256, 256)
    assert fa.pick_blocks(t, t, head_dim=256) == (512, 512)
    # a head's float32 dq stays in VMEM through the fused call: 8 MiB
    assert fa.dq_resident_bytes(t, 256) == 8 * 2**20
    for itemsize, tile in ((2, 512), (4, 256)):
        asked = fa.fused_vmem_bytes(t, 256, tile, itemsize, 256)
        assert fa.dq_resident_bytes(t, 256) < asked <= fa.VMEM_ASK_BOUND_BYTES
        assert fa.backward_form(t, t, 256, itemsize, value_dim=256) == "fused"
    # wider than Ling's keys of 192 over values of 128 in the same tiles
    assert fa.fused_vmem_bytes(t, 256, 512, 2, 256) > fa.fused_vmem_bytes(
        t, 192, 512, 2, 128)


# (sequence, key width, value width) of every cell's causal flash layers, the
# float32 tile ``pick_blocks`` gives there and the VMEM the fused float32
# call asks for: what the cells' MATCHED checks run, with the three bfloat16
# parts of seven operand tiles that PR 53 added to the count
CELLS = {
    "ouro-2.6b": (4096, 128, 128, 512, 18_481_152),
    "granite-4.0-h-micro": (8192, 64, 64, 512, 20_578_304),
    "lfm2-8b-a1b": (8192, 64, 64, 512, 20_578_304),
    "smallthinker-21b-a3b": (16384, 128, 128, 512, 24_772_608),
    "olmo-hybrid-7b": (8192, 128, 128, 512, 20_578_304),
    "ling-3.0-flash": (8192, 192, 128, 256, 16_449_536),
    "glm-4.7-flash": (8192, 256, 256, 256, 17_956_864),
}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_cells_float32_backward_keeps_its_form_and_says_its_ask(cell):
    """``fused_vmem_bytes`` is the ask AND what ``backward_form`` decides
    by: the float32 parts move neither a cell's form nor its tiles, and its
    ask by exactly 3 parts x 7 tiles x 2 bytes."""
    t, d, dv, tile, asked = CELLS[cell]
    assert fa.pick_blocks(t, t, head_dim=d, itemsize=4,
                          value_dim=dv) == (tile, tile)
    assert fa.fused_vmem_bytes(t, d, tile, 4, dv) == asked
    lanes = tile * (4 * fa._lanes(d) + 3 * fa._lanes(dv))
    assert asked - 3 * 2 * lanes == (
        fa.dq_resident_bytes(t, d) + tile * (fa._lanes(d) + fa._lanes(dv)) * 4
        + 2 * (lanes * 4 + 2 * tile * 128 * 4) + 8 * tile * tile * 4)
    assert asked < fa.VMEM_ASK_BOUND_BYTES // 3
    for itemsize in (2, 4):
        assert fa.backward_form(t, t, d, itemsize, value_dim=dv) == "fused"


def test_where_the_float32_form_turns_at_heads_of_128():
    """The count decides the form only where a head's resident dq nears the
    96 MiB a call may ask for: at heads of 128 in float32 the fused call
    holds to 163,840 rows (the three parts are 2.6 MiB of the 95.6 there)
    and bf16's 1024-row tiles to 65,536; no cell trains a tenth of that."""
    assert fa.backward_form(163_840, 163_840, 128, 4) == "fused"
    assert fa.backward_form(172_032, 172_032, 128, 4) == "two_call"
    assert fa.backward_form(65_536, 65_536, 128, 2) == "fused"
    assert fa.backward_form(131_072, 131_072, 128, 2) == "two_call"


@pytest.mark.parametrize("blocks, block_q, block_k, dtype", [
    (1, 32, 16, jnp.float32), (2, 16, 32, jnp.bfloat16),
    (4, 16, 16, jnp.bfloat16), (8, 32, 16, jnp.float32),
    (8, 16, 16, jnp.bfloat16), (16, 16, 16, jnp.float32)])
def test_the_live_grid_gives_the_rectangular_grids_bits_at_256(
        blocks, block_q, block_k, dtype):
    """ISSUE 54 at the GLM cell's widths: the causal calls step over the
    tiles under the diagonal alone, and give the rectangular grid's bits
    (the cover of the cases: ``test_flash_attention.py``)."""
    from flash_grid_cases import live_grid_against_rectangular

    live_grid_against_rectangular(blocks, block_q, block_k, D, D, dtype)
