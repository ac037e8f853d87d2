"""The DLRM step of both ``dlrm-criteo-kaggle`` cells compiled for a described
TPU v5e: the row kernels, the feature-major interaction, and what the step no
longer copies (``tpu_compile_helpers`` says how and why)."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from tpu_compile_helpers import (  # noqa: F401 - fixtures by name
    kernels_compile, no_compile_cache, on_chip, one_chip)


# the Criteo-Kaggle cardinalities of benchmark/configs/dlrm-criteo-kaggle.json
CRITEO_KAGGLE = (
    1460, 583, 10131227, 2202608, 305, 24, 12517, 633, 3, 93145, 5683,
    8351593, 3194, 27, 14992, 5461306, 10, 5652, 2173, 4, 7046547, 18, 15,
    286181, 105, 142572)
ROW_TABLES = (10131227, 2202608, 93145, 8351593, 5461306, 7046547, 286181,
              142572)
# of them, those XLA's gather would copy whole: the gather kernel reads them
GATHERED_TABLES = (93145, 286181, 142572)


def _table_results(text, ops, layout=""):
    """The instructions of ``ops`` whose result is a row-path table, as
    ``[V, 16]`` or as the kernel's ``[16, V]`` view."""
    shapes = "|".join(f"{v},16|16,{v}" for v in ROW_TABLES)
    return re.findall(
        rf"= f32\[(?:{shapes})\]\{{{layout}[^}}]*\}} (?:{ops})\(", text)


def _dlrm_step(one_chip, interaction_kernel):
    """The benchmark's DLRM step (batch 2048, the 26 Criteo-Kaggle tables,
    Adagrad; under ``kernels_compile`` the plan takes both row kernels), its
    interaction through the Mosaic kernel or through XLA: ``(params, plan,
    compiled, forward)``, ``compiled(kernel_paths, gather_paths)`` the step
    and ``forward()`` the model alone (the evaluation's program), compiled
    for the described chip."""
    import optax

    from raydp_tpu.estimator import row_update
    from raydp_tpu.estimator.jax_estimator import _LOSSES, make_train_step
    from raydp_tpu.models import DLRM

    batch = 2048
    module = DLRM(vocab_sizes=CRITEO_KAGGLE, num_dense=13, embed_dim=16,
                  bottom_mlp=(512, 256, 64), top_mlp=(512, 256),
                  use_pallas_interaction=interaction_kernel)

    place = functools.partial(on_chip, one_chip=one_chip)
    x = place((jax.ShapeDtypeStruct((batch, 13), jnp.float32),
                 jax.ShapeDtypeStruct((batch, 26), jnp.int32)))
    y = place(jax.ShapeDtypeStruct((batch,), jnp.float32))
    params = place(jax.eval_shape(module.init, jax.random.PRNGKey(0), x))
    tx = optax.adagrad(0.01)
    state = place(jax.eval_shape(tx.init, params))
    plan = row_update.plan(module, tx, params, x, batch)

    def compiled(kernel_paths, gather_paths):
        step = make_train_step(module, _LOSSES["bce"], tx, plan.paths,
                               kernel_paths, gather_paths)
        return jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            params, state, place(jax.ShapeDtypeStruct((), jnp.float32)),
            x, y).compile()

    def forward():
        return jax.jit(module.apply).lower(params, x).compile()

    return params, plan, compiled, forward


def test_dlrm_step_writes_rows_back_with_the_kernel(
        one_chip, no_compile_cache, kernels_compile):
    """The benchmark's DLRM step (batch 2048, the 26 Criteo-Kaggle tables,
    Adagrad) with both kernels, against the same step through XLA's gather
    (PR 28's step) and through XLA's scatter too (PR 25's step, text for
    text): eight write-back calls (a table and its accumulator each) and
    three gather calls (the tables XLA's gather would copy) on bitcasts of
    the tables, no operation whose result is a whole row-path table besides
    the kernels, no second copy of a table among the temporaries."""
    params, plan, compiled, _ = _dlrm_step(one_chip, False)
    assert sorted(params["params"][p[1]].shape[0] for p in plan.paths) == sorted(
        ROW_TABLES)
    assert plan.stats()["write_back"] == {
        "kernel": 16, "scatter": 0, "reason": ""}
    read = plan.stats()["gather"]
    assert (read["kernel"], read["xla"]) == (6, 10) and "rows" in read["reason"]
    assert sorted(params["params"][p[1]].shape[0]
                  for p in plan.gather_paths) == sorted(GATHERED_TABLES)

    kernel = compiled(plan.kernel_paths, plan.gather_paths)
    gather, scatter = compiled(plan.kernel_paths, ()), compiled((), ())
    text, xla_reads, parent = (c.as_text() for c in (kernel, gather, scatter))
    writes, reads = (rf"%{name}[\w.\-]* = " for name in (
        "row_write_back", "row_gather"))
    assert len(re.findall(writes, text)) == 8
    assert len(re.findall(reads, text)) == len(GATHERED_TABLES)
    # the instructions, not the bare name: the text's table of source files
    # names tests/test_row_write_back.py when this worker ran it before
    assert not re.findall(writes, parent) and not re.findall(reads, parent)
    assert len(re.findall(writes, xla_reads)) == 8
    assert not re.findall(reads, xla_reads)
    assert len(_table_results(parent, "scatter")) == 16
    # the kernels' operands and results are the tables themselves
    assert not _table_results(text, "scatter|transpose")
    # (the write-back's 32 views, in and out, and the gather's six)
    assert len(_table_results(text, "bitcast")) == 32 + 6
    # XLA's scatter copies the three tables of 93,145-286,181 rows to the
    # row-major layout and back, and XLA's gather copies them there to read
    # them ({1,0}; the tables' own layout is {0,1}); with both kernels no
    # table is copied in either orientation, for either view, or moved to
    # VMEM for a call (by halves: ``slice-done f32[V,8]``)
    copies = "copy|copy-done"
    assert _table_results(parent, copies, layout="0,1")
    assert len(_table_results(xla_reads, copies, layout="1,0")) == 6
    assert not _table_results(xla_reads, copies, layout="0,1")
    assert not _table_results(text, copies)
    halves = "|".join(f"{v},8" for v in ROW_TABLES)
    assert not re.findall(rf"= f32\[(?:{halves})\]", text)
    assert (kernel.memory_analysis().temp_size_in_bytes
            <= gather.memory_analysis().temp_size_in_bytes
            <= scatter.memory_analysis().temp_size_in_bytes)


# the top-level ``copy`` and ``transpose`` of a row block in the row-major
# form, ``f32[2048,16]{1,0}`` (16 columns padded to 128 lanes, 1 MB for 128
# KB), that the step and the model's forward pass compile to (PR 47): XLA's
# gather takes and gives row-major blocks only, so a block is turned once on
# its way to its [16, 2048] slab and its cotangent once on its way back
ROW_MAJOR_TURNS = {"step": 70, "forward": 26}


@pytest.mark.parametrize("program", ["step", "forward"])
def test_dlrm_row_blocks_reach_the_interaction_feature_major(
        one_chip, no_compile_cache, kernels_compile, program):
    """The same step with the interaction's Mosaic kernel, and the model's
    forward pass alone (the evaluation's program, 26 takes): no instruction
    builds a ``f32[2048,1,16]`` block in any layout (the parent's step: 243,
    27 of them ``copy``; a block pads 8- to 128-fold), the operand is
    ``f32[27,16,2048]``, ONE ``dlrm_interaction`` call whose result is
    ``f32[2048,351]`` (``kernel.interaction_roofline`` finds it by that), no
    result ``f32[rows,16]`` of 10,000 rows or more besides the dense tables'
    (``estimator.table_update_ms`` sums those)."""
    _, plan, compiled, forward = _dlrm_step(one_chip, True)
    text = (compiled(plan.kernel_paths, plan.gather_paths)
            if program == "step" else forward()).as_text()
    assert not re.findall(r"= f32\[2048,1,16\]", text)
    assert re.findall(r"= f32\[27,16,2048\]\{2,1,0", text)
    calls = re.findall(
        r"%[\w.\-]*dlrm_interaction[\w.\-]* = (\S+?)\{\S* custom-call\(", text)
    assert calls == ["f32[2048,351]"]
    assert len(re.findall(r"= f32\[\d+,351\]\S* custom-call\(", text)) == 1
    turns = re.findall(
        r"^\s+(?:ROOT )?%[\w.\-]+ = f32\[2048,16\]\{1,0[^}]*\} "
        r"(?:copy|transpose)\(", text, re.M)
    assert len(turns) <= ROW_MAJOR_TURNS[program]
    dense = {f"f32[{v},16]" for v in CRITEO_KAGGLE if v not in ROW_TABLES}
    assert set(re.findall(r"= (f32\[\d{5,},16\])", text)) - {
        f"f32[{v},16]" for v in ROW_TABLES} <= dense
    # (a row-path table's own `f32[V,16]` lines in the step are its
    # parameter, its bitcasts into the kernels' views and XLA's gathers of
    # the five large ones: none of them an operation ON a table. The forward
    # pass alone has no row path: XLA's gather copies the three tables of
    # 93,145-286,181 rows row-major to read them, in every evaluation batch:
    # ROADMAP Queue 1 item 1 (f))
    copied = _table_results(text, "scatter|transpose|copy|copy-done")
    assert len(copied) == (0 if program == "step" else len(GATHERED_TABLES))
