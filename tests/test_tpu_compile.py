"""Compile the main path's kernels at real widths for a DESCRIBED TPU v5e (no
chip here: the TPU's compiler is installed, on-chip-measurement guide §2).
What interpret mode cannot show: VMEM budgets and tile alignment. Keep every
such test in THIS file: one process holds the TPU library at a time, and the
topology is described inside a fixture, never at import."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("dtype, precision, tile", [
    (jnp.bfloat16, None, 1024), (jnp.float32, "highest", 512)])
def test_flash_forward_and_backward_compile_at_ouro_widths(
        one_chip, no_compile_cache, dtype, precision, tile):
    """[2 x 16 heads, T 4096, head 128], causal: the timed bf16 step's tiles,
    and the float32 ones of the benchmark's matched check (1024-row float32
    tiles ask the backward kernel for 19.5 MB of its 16 MB of VMEM)."""
    fa = importlib.import_module("raydp_tpu.ops.flash_attention")
    assert fa.pick_blocks(4096, 4096, head_dim=128,
                          itemsize=jnp.dtype(dtype).itemsize) == (tile, tile)
    q = jax.ShapeDtypeStruct((2, 16, 4096, 128), dtype, sharding=one_chip)

    def grads(q, k, v):
        with jax.default_matmul_precision(precision):
            return jax.grad(
                lambda q, k, v: fa.flash_attention(
                    q, k, v, True, None, None, False
                ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(q, q, q).compile().as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        # the instruction's name: %jvp_<name>_.1, %transpose_jvp_<name>__.1
        assert re.search(rf"%[\w.\-]*{name}[\w.\-]* = ", text), name
    assert text.count("tpu_custom_call") >= 3
