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


# the Criteo-Kaggle cardinalities of benchmark/configs/dlrm-criteo-kaggle.json
CRITEO_KAGGLE = (
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
    8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15,
    286181, 105, 142572)
ROW_TABLES = (10131227, 2202608, 93145, 8351593, 5461306, 7046547, 286181,
              142572)


def _table_results(text, ops, layout=""):
    """The instructions of ``ops`` whose result is a row-path table, as
    ``[V, 16]`` or as the kernel's ``[16, V]`` view."""
    shapes = "|".join(f"{v},16|16,{v}" for v in ROW_TABLES)
    return re.findall(
        rf"= f32\[(?:{shapes})\]\{{{layout}[^}}]*\}} (?:{ops})\(", text)


def test_dlrm_step_writes_rows_back_with_the_kernel(
        one_chip, no_compile_cache, monkeypatch):
    """The benchmark's DLRM step (batch 2048, the 26 Criteo-Kaggle tables,
    Adagrad) with the write-back kernel, against the same step through XLA's
    scatter (the parent commit's step, text for text): eight kernel calls
    (a table and its accumulator each) on bitcasts of the tables, no table
    written back whole, no second copy of a table among the temporaries."""
    import optax

    from raydp_tpu.estimator import row_update
    from raydp_tpu.estimator.jax_estimator import _LOSSES, make_train_step
    from raydp_tpu.models import DLRM
    from raydp_tpu.ops import backend

    # what the TPU backend would say of itself (ops/backend.py): the plan
    # then takes the kernel, and the kernel compiles and is not interpreted
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    batch = 2048
    module = DLRM(vocab_sizes=CRITEO_KAGGLE, num_dense=13, embed_dim=16,
                  bottom_mlp=(512, 256, 64), top_mlp=(512, 256),
                  use_pallas_interaction=False)

    def on_chip(tree):
        return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
            leaf.shape, leaf.dtype, sharding=one_chip), tree)

    x = on_chip((jax.ShapeDtypeStruct((batch, 13), jnp.float32),
                 jax.ShapeDtypeStruct((batch, 26), jnp.int32)))
    y = on_chip(jax.ShapeDtypeStruct((batch,), jnp.float32))
    params = on_chip(jax.eval_shape(module.init, jax.random.PRNGKey(0), x))
    tx = optax.adagrad(0.01)
    state = on_chip(jax.eval_shape(tx.init, params))
    plan = row_update.plan(module, tx, params, x, batch)
    assert sorted(params["params"][p[1]].shape[0] for p in plan.paths) == sorted(
        ROW_TABLES)
    assert plan.stats()["write_back"] == {
        "kernel": 16, "scatter": 0, "reason": ""}

    def compiled(kernel_paths):
        step = make_train_step(module, _LOSSES["bce"], tx, plan.paths,
                               kernel_paths)
        return jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            params, state, on_chip(jax.ShapeDtypeStruct((), jnp.float32)),
            x, y).compile()

    kernel, scatter = compiled(plan.kernel_paths), compiled(())
    text, parent = kernel.as_text(), scatter.as_text()
    calls = r"%row_write_back[\w.\-]* = "
    assert len(re.findall(calls, text)) == 8
    # the instructions, not the bare name: the text's table of source files
    # names tests/test_row_write_back.py when this worker ran it before
    assert not re.findall(calls, parent)
    assert len(_table_results(parent, "scatter")) == 16
    # the kernel's operands and results are the tables themselves
    assert not _table_results(text, "scatter|transpose")
    assert len(_table_results(text, "bitcast")) == 32
    # XLA's scatter copies the three tables of 93,145-286,181 rows to the
    # row-major layout and back; with the kernel no table comes back whole
    # ({0,1}: the tables' own layout), none is copied for the [16, V] view,
    # and what is left is the copy that XLA's gather (``_take``) reads
    copies = "copy|copy-done"
    assert _table_results(parent, copies, layout="0,1")
    assert not _table_results(text, copies, layout="0,1")
    assert not [c for c in _table_results(text, copies) if "[16," in c]
    assert 2 * len(_table_results(text, copies)) <= len(
        _table_results(parent, copies))
    assert (kernel.memory_analysis().temp_size_in_bytes
            <= scatter.memory_analysis().temp_size_in_bytes)
