"""Compile the main path's kernels at real widths for a DESCRIBED TPU v5e (no
chip here: the TPU's compiler is installed, on-chip-measurement guide §2).
What interpret mode cannot show: VMEM budgets and tile alignment. Keep every
such test in THIS file: one process holds the TPU library at a time, and the
topology is described inside a fixture, never at import."""

import functools
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


@pytest.fixture()
def kernels_compile(monkeypatch):
    # what the TPU backend would say of itself (ops/backend.py): a kernel
    # whose ``interpret`` is left to the rule compiles and is not interpreted
    # (one chip of it: with several devices and no mesh a Mosaic call that
    # XLA would have to partition falls back, ops/interaction.py)
    from raydp_tpu.ops import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda *_: 1)


def _instructions(text):
    """The instructions of a compiled program's text: what a change that
    leaves a model's options as they were must not move."""
    return len(re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = ", text, re.M))


# the two-call pass's second call (PR 43: gone from the cells' programs;
# ``flash_attention_bwd_dq_dkv`` does not match)
BWD_DKV = r"%[\w.\-]*flash_attention_(?:window_)?bwd_dkv"


def _on_chip(tree, one_chip):
    """The shapes of ``tree`` as arguments that live on the described chip."""
    return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=one_chip), tree)


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
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        # the instruction's name: %jvp_<name>_.1, %transpose_jvp_<name>__.1
        assert re.search(rf"%[\w.\-]*{name}[\w.\-]* = ", text), name
    # PR 43: the backward pass is ONE call
    assert not re.search(BWD_DKV, text)
    assert text.count("tpu_custom_call") == 2


@pytest.mark.parametrize("heads, t, head, window", [
    (32, 4096, 128, None), (32, 8192, 64, None), (128, 8192, 64, None),
    (56, 16384, 128, None), (56, 16384, 128, 4096)],
    ids=["ouro", "granite", "routed", "window_cell_global",
         "window_cell_window"])
@pytest.mark.parametrize("dtype, precision", [
    (jnp.bfloat16, None), (jnp.float32, "highest")],
    ids=["bf16", "float32_matched"])
def test_fused_flash_backward_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, heads, t, head, window, dtype, precision):
    """PR 43: the ONE-call backward pass alone, [batch x heads, T, head] of
    the four LM cells (the timed bf16 step and the matched check's float32
    tiles): a head's float32 dq lives in VMEM (2-8 MB) beside the tile, so
    the call asks for more than a Mosaic call's 16 MB by
    ``vmem_limit_bytes`` (``fused_vmem_bytes``, from the shapes): what it
    asks for must cover what the compiler needs, here and not on the chip."""
    fa = importlib.import_module("raydp_tpu.ops.flash_attention")
    itemsize = jnp.dtype(dtype).itemsize
    block, _ = fa.pick_blocks(t, t, head_dim=head, itemsize=itemsize)
    assert fa.backward_form(t, t, head, itemsize) == "fused"
    assert (fa.dq_resident_bytes(t, head) < fa.fused_vmem_bytes(
        t, head, block, itemsize) <= fa.VMEM_ASK_BOUND_BYTES)
    q = jax.ShapeDtypeStruct((1, heads, t, head), dtype, sharding=one_chip)
    stat = jax.ShapeDtypeStruct((1, heads, t), jnp.float32, sharding=one_chip)

    def backward(q, k, v, lse, dsum, g):
        with jax.default_matmul_precision(precision):
            return fa.flash_backward_blocks(
                q, k, v, lse, dsum, g, 0, 0, True, None, None, False, window)

    compiled = jax.jit(backward).lower(q, q, q, stat, stat, q).compile()
    text = compiled.as_text()
    name = "flash_attention_window_bwd_dq_dkv" if window else (
        "flash_attention_bwd_dq_dkv")
    assert len(re.findall(rf"%[\w.\-]*{name}[\w.\-]* = ", text)) == 1
    assert text.count("tpu_custom_call") == 1
    # dq leaves the call in the operands' dtype: no float32 [heads, T, head]
    # array anywhere in the program when the operands are bf16
    if dtype == jnp.bfloat16:
        assert f"f32[{heads},{t},{head}]" not in text


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

    on_chip = functools.partial(_on_chip, one_chip=one_chip)
    x = on_chip((jax.ShapeDtypeStruct((batch, 13), jnp.float32),
                 jax.ShapeDtypeStruct((batch, 26), jnp.int32)))
    y = on_chip(jax.ShapeDtypeStruct((batch,), jnp.float32))
    params = on_chip(jax.eval_shape(module.init, jax.random.PRNGKey(0), x))
    tx = optax.adagrad(0.01)
    state = on_chip(jax.eval_shape(tx.init, params))
    plan = row_update.plan(module, tx, params, x, batch)

    def compiled(kernel_paths, gather_paths):
        step = make_train_step(module, _LOSSES["bce"], tx, plan.paths,
                               kernel_paths, gather_paths)
        return jax.jit(step, donate_argnums=(0, 1, 2)).lower(
            params, state, on_chip(jax.ShapeDtypeStruct((), jnp.float32)),
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


# -- the looped LM's gradient at the published widths --------------------------

OURO = dict(vocab_size=49152, attn_impl="flash")  # LoopLM's defaults are the rest
FLASH_FWD = r"%[\w.\-]*flash_attention_fwd[\w.\-]* = "


def _loss_products(text, scope):
    """The matrix products (the TPU compiler's ``convolution``s) under the
    loss's named scope: logits, the gradient back to the state, the head's
    gradient; a fourth would be a chunk's logits rebuilt (PR 32)."""
    return len([line for line in text.splitlines()
                if " convolution(" in line and scope in line])


def _looplm_gradient(one_chip, rows, tokens, matched=False, **kw):
    """The gradient of ``LoopLM.loss`` compiled for the described chip: the
    timed step's bf16 form, or the float32 / highest / ``with_states`` form
    of the benchmark's ``matched`` check."""
    from raydp_tpu.models import LoopLM

    module = LoopLM(**OURO, dtype=jnp.float32 if matched else jnp.bfloat16, **kw)
    x = jax.ShapeDtypeStruct((rows, tokens + 1), jnp.int32, sharding=one_chip)
    params = _on_chip(jax.eval_shape(
        lambda r, s: module.init(r, s, None, method="loss"),
        jax.random.PRNGKey(0), x), one_chip)

    def grads(p, x):
        with jax.default_matmul_precision("highest" if matched else None):
            return jax.value_and_grad(
                lambda p: module.apply(p, x, None, matched, method="loss"),
                has_aux=True)(p)

    return jax.jit(grads).lower(params, x).compile()


@pytest.mark.parametrize("matched, temp_limit", [(False, 7.0e9), (True, 10.0e9)],
                         ids=["bf16", "float32_matched"])
def test_looplm_gradient_keeps_what_the_flash_forward_gave(
        one_chip, no_compile_cache, kernels_compile, matched, temp_limit):
    """Batch 2 x 4096 at the published widths, blocks recomputed: the
    compiled gradient holds as many flash forward calls as one that
    recomputes nothing (``remat=False``, which fits the chip only at a
    smaller batch: 1 x 1024), one a layer: the kept ``attn_out`` and
    ``attn_lse`` make the recomputed call dead code (a bare
    ``jax.checkpoint`` compiled 12). And what is kept fits: temporaries
    5.62 GB (bf16) and 9.47 GB (float32) here with all three names and the
    exits' loss taken once after the loop, its gradient in the forward
    sweep (PR 32; 6.72 and 9.65 with the loss inside the loop and its
    logits recomputed, PR 30, from 4.28 and 5.06 with no name kept)."""
    kept = _looplm_gradient(one_chip, 2, 4096, matched)
    plain = _looplm_gradient(one_chip, 1, 1024, matched, remat=False)
    calls = len(re.findall(FLASH_FWD, kept.as_text()))
    assert calls == len(re.findall(FLASH_FWD, plain.as_text())) == 6
    assert kept.memory_analysis().temp_size_in_bytes <= temp_limit
    assert _loss_products(kept.as_text(), "looplm.exit_loss") == 3
    # what a change that leaves the model's options alone must not move
    # (12,395 until PR 43 made the flash backward one call of two)
    if not matched:
        assert _instructions(kept.as_text()) == 12_341


@pytest.mark.parametrize("remat, calls_a_layer", [(False, 2), (True, 3)],
                         ids=["no_remat", "remat"])
def test_transformer_flash_gradient_is_the_program_it_was(
        one_chip, no_compile_cache, kernels_compile, remat, calls_a_layer):
    """``flash_attention`` names its residuals for a policy that saves by
    name; ``TransformerLM`` has none (``nn.remat`` bare, or no remat), so a
    name is the identity and its gradient compiles to the Mosaic calls it
    had but for the backward pass's two being one since PR 43: forward and
    the fused backward a layer, and the forward again under remat."""
    from raydp_tpu.models.transformer import TransformerLM

    layers = 2
    module = TransformerLM(vocab_size=8192, d_model=2048, num_heads=16,
                           num_layers=layers, attn_impl="flash", remat=remat)
    x = jax.ShapeDtypeStruct((1, 4096), jnp.int32, sharding=one_chip)
    params = _on_chip(jax.eval_shape(module.init, jax.random.PRNGKey(0), x),
                      one_chip)
    text = jax.jit(jax.grad(
        lambda p, x: module.apply(p, x).astype(jnp.float32).sum()
    )).lower(params, x).compile().as_text()
    assert text.count("tpu_custom_call") == calls_a_layer * layers
    assert len(re.findall(FLASH_FWD, text)) == (2 if remat else 1) * layers


# -- the hybrid state-space LM's epoch program at the published widths ---------


@pytest.mark.parametrize("matched", [False, True], ids=["bf16", "float32_matched"])
def test_flash_kernels_compile_at_head_dim_64(one_chip, no_compile_cache, matched):
    """[1 x 32 heads, T 8192, head 64], causal: the grouped-query layer's
    kernels after K and V are repeated to the query heads (the flash
    kernels had only ever run heads of 128), in the timed step's bf16
    (1024-row tiles) and in the float32 of the benchmark's matched check
    (512: a head of 64 is padded to the 128 lanes in VMEM, and 1024-row
    float32 tiles ask the forward kernel for 16.44 MB of its 16)."""
    fa = importlib.import_module("raydp_tpu.ops.flash_attention")
    dtype = jnp.float32 if matched else jnp.bfloat16
    tile = 512 if matched else 1024
    assert fa.pick_blocks(8192, 8192, head_dim=64,
                          itemsize=jnp.dtype(dtype).itemsize) == (tile, tile)
    q = jax.ShapeDtypeStruct((1, 32, 8192, 64), dtype, sharding=one_chip)

    def grads(q, k, v):
        with jax.default_matmul_precision("highest" if matched else None):
            return jax.grad(
                lambda q, k, v: fa.flash_attention(
                    q, k, v, True, None, None, False
                ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(q, q, q).compile().as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert re.search(rf"%[\w.\-]*{name}[\w.\-]* = ", text), name
    assert not re.search(BWD_DKV, text)


def test_hybridlm_epoch_program_fits_the_chip(
        one_chip, no_compile_cache, kernels_compile):
    """Step 0 of ISSUE 31: the benchmark's epoch program of
    ``granite-4.0-h-micro.pretrain-8k`` (849.2 M float32 parameters, AdamW,
    3 steps of 1 x 8192 tokens gathered from the resident rows and scanned,
    parameters and optimizer state donated: what the resident scan runner
    compiles) for the described v5e: arguments + outputs - aliased +
    temporaries within 15.5e9 bytes (13.78e9 here: arguments 10.19e9, all
    aliased, temporaries 3.59e9; 13.66e9 before PR 32 put the loss's
    gradient, with its accumulator, into the forward sweep), one flash forward and one fused backward
    call at head_dim 64 (the attention layer's ``attn_out`` and ``attn_lse``
    are kept, so the backward pass recomputes none), three products in the
    loss (no chunk's logits computed twice)."""
    from raydp_tpu.estimator.jax_estimator import (
        MODEL_LOSS, _scan_over_batches, make_train_step)
    from raydp_tpu.models import HybridLM, hybridlm_optimizer

    steps, tokens = 3, 8192
    module = HybridLM(vocab_size=50176, attn_impl="flash")  # published widths
    on_chip = functools.partial(_on_chip, one_chip=one_chip)
    rows = on_chip(jax.ShapeDtypeStruct((steps, tokens + 1), jnp.int32))
    perm = on_chip(jax.ShapeDtypeStruct((steps,), jnp.int32))
    params = on_chip(jax.eval_shape(
        lambda r, s: module.init(r, s, None, method="loss"),
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, tokens + 1), jnp.int32)))
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == 849_230_784
    tx = hybridlm_optimizer()
    state = on_chip(jax.eval_shape(tx.init, params))
    step = make_train_step(module, MODEL_LOSS, tx)

    def epoch(params, state, rows, perm):
        return _scan_over_batches(
            step, params, state, rows[perm].reshape(steps, 1, tokens + 1), None)

    compiled = jax.jit(epoch, donate_argnums=(0, 1)).lower(
        params, state, rows, perm).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    assert held <= 15.5e9, held
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert len(re.findall(rf"%[\w.\-]*{name}[\w.\-]* = ", text)) == 1, name
    assert not re.search(BWD_DKV, text)
    assert _loss_products(text, "hybridlm.loss") == 3
    # what a change that leaves the model's options alone must not move
    # (30,285 until PR 43 made the flash backward one call of two)
    assert _instructions(text) == 30_162


# -- the routed-experts LM's grouped products and epoch program ------------------


@pytest.mark.parametrize("impl, kernel", [
    ("ragged_dot", "ragged-dot"), ("megablox", "gmm")])
def test_grouped_products_compile_at_lfm2_widths(
        one_chip, no_compile_cache, kernels_compile, impl, kernel):
    """[131,072 rows x 2048] x [8 experts, 2048, 3584] and its gradients
    (the rows' and the weights'), bf16 operands, ragged groups: both
    implementations of ``ops.experts.grouped_dot`` are Mosaic calls on the
    chip (XLA:TPU runs ``lax.ragged_dot`` as a kernel of its own), named so
    that ``harness/moe_costs.GMM`` finds them in a trace."""
    from benchmark.harness import moe_costs
    from raydp_tpu.ops import experts

    x = jax.ShapeDtypeStruct((131072, 2048), jnp.bfloat16, sharding=one_chip)
    w = jax.ShapeDtypeStruct((8, 2048, 3584), jnp.float32, sharding=one_chip)
    sizes = jax.ShapeDtypeStruct((8,), jnp.int32, sharding=one_chip)

    def grads(x, w, sizes):
        return jax.grad(lambda x, w: experts.grouped_dot(
            x, w, sizes, impl).astype(jnp.float32).sum(), argnums=(0, 1))(x, w)

    text = jax.jit(grads).lower(x, w, sizes).compile().as_text()
    calls = re.findall(rf"(%[\w.\-]*{kernel}[\w.\-]*) = \S+ custom-call", text)
    assert len([c for c in calls if "metadata" not in c]) == 2, calls
    assert all(moe_costs.GMM.search(c) for c in calls)


def test_routed_hybridlm_epoch_program_fits_the_chip(
        one_chip, no_compile_cache, kernels_compile):
    """ISSUE 34: the benchmark's epoch program of
    ``lfm2-8b-a1b.pretrain-8k-routed`` (507.8 M float32 parameters, AdamW, 3
    steps of 4 x 8192 tokens gathered from the resident rows and scanned,
    parameters and optimizer state donated, the steps' report summed) for
    the described v5e, AdamW under its warm-up with the balancing rule on
    the biases. ISSUE 35: each expert layer runs at the LIKELY rows' bound
    (SLACK x the even share) with the worst case (tokens x 4 = 131,072 rows:
    no pair can be dropped) as the overflow's arm of a conditional, one
    forward and one in the backward pass. The two arms' temporaries share
    memory: the program holds what it held with the worst case alone
    (13.01e9 bytes), one flash forward and one fused backward call, and
    the grouped product is a Mosaic call 32 times IN EITHER ARM (4 expert
    layers x (2 forward + 2 in the backward pass's own forward + 4
    backward): a third forward would make it 40), and no arm returns an
    array of the worst-case rows (a residual of the arm not taken, written
    as zeros). ISSUE 40: in a likely arm the token side gathers each held
    row once, a row one tile: an expert layer's forward arm holds the
    dispatch's gather (40,960 rows of [2048]) and the combine's two (40,960
    + 3 rows into token order, 32,768 first slots, rows of [16, 128]), its
    backward arm the dispatch's again, the result's cotangent a row, and
    the two of the dispatch's backward sum: 7 a layer where a gather a
    choice made 15."""
    import collections
    import json
    import os

    from raydp_tpu.estimator.jax_estimator import (
        MODEL_LOSS, _scan_over_batches, make_train_step)
    from raydp_tpu.models import RoutedHybridLM, hybridlm_optimizer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        config = json.load(f)
    steps, batch, tokens = 3, 4, 8192
    module = RoutedHybridLM.from_config(config, **config["model"]["kwargs"])
    worst = module.expert_row_bound(batch * tokens)
    assert worst == batch * tokens * 4
    assert module.expert_likely_row_bound(batch * tokens) < worst
    on_chip = functools.partial(_on_chip, one_chip=one_chip)
    rows = on_chip(jax.ShapeDtypeStruct((steps * batch, tokens + 1), jnp.int32))
    perm = on_chip(jax.ShapeDtypeStruct((steps * batch,), jnp.int32))
    params = on_chip(jax.eval_shape(
        lambda r, s: module.init(r, s, None, method="loss"),
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, tokens + 1), jnp.int32)))
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == 507_820_288
    # AdamW under the warm-up, the balancing rule on the biases
    tx = hybridlm_optimizer(**config["model"]["adamw"])
    state = on_chip(jax.eval_shape(tx.init, params))
    step = make_train_step(module, MODEL_LOSS, tx)

    def epoch(params, state, rows, perm):
        return _scan_over_batches(
            step, params, state,
            rows[perm].reshape(steps, batch, tokens + 1), None)

    compiled = jax.jit(epoch, donate_argnums=(0, 1)).lower(
        params, state, rows, perm).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print("routed epoch program holds", held)
    # the parent's program, the worst case alone, held 13,006,128,128
    assert held <= 13.05e9, held
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert len(re.findall(rf"%[\w.\-]*{name}[\w.\-]* = ", text)) == 1, name
    assert not re.search(BWD_DKV, text)
    conditionals = re.findall(r"= (\(.*?\)) conditional\(", text)
    assert len(conditionals) == 8
    assert not any(f"[{worst}," in result for result in conditionals)
    assert len(re.findall(r"%[\w.\-]*gmm[\w.\-]* = \S+ custom-call", text)) == 64
    assert _loss_products(text, "hybridlm.loss") == 3
    # the likely arm is the conditional's branch 1 (its predicate true)
    likely = collections.Counter(
        shape for shape, name in re.findall(
            r"= bf16\[(\d+,(?:2048|16,128))\]\S* gather\(.*?op_name=\"([^\"]*)\"",
            text) if "branch_1_fun" in name)
    assert likely == {"40960,2048": 4 * 3, "40963,16,128": 4 * 2,
                      "32768,16,128": 4 * 2}, likely
    # the epoch's report leaves the program: [expert layers, held] and a count
    assert "f32[4,8]" in text.split("ENTRY")[1].split("\n")[0]
    # what a change that leaves the model's options alone must not move
    # (31,024 until PR 43 made the flash backward one call of two: one
    # Mosaic call fewer, and the compiler schedules 199 instructions more)
    assert _instructions(text) == 31_223


# -- window layers over routed experts (PR 42) -----------------------------------


@pytest.mark.parametrize("dtype, precision, tile, k_steps, q_steps", [
    (jnp.bfloat16, None, 1024, 5, 5), (jnp.float32, "highest", 512, 9, 9)])
def test_window_kernels_compile_with_a_grid_that_follows_the_window(
        one_chip, no_compile_cache, dtype, precision, tile, k_steps, q_steps):
    """[2 x 28 heads, T 16,384, head 128], a window of 4096: the timed bf16
    step's tiles and the float32 ones of the matched check. The k axis of
    the forward and dq grids has the k-blocks a q-block's window can touch
    (5 at 1024-row tiles: never more than ceil((W + block_q - 1) / block_k)
    + 1 = 6), NOT T / block_k = 16; the backward call's q axis likewise
    (PR 43: one fused call in the dk/dv grid). The calls compile for the
    described v5e under their own names."""
    import math

    fa = importlib.import_module("raydp_tpu.ops.flash_attention")
    t, window = 16384, 4096
    assert fa.pick_blocks(t, t, head_dim=128,
                          itemsize=jnp.dtype(dtype).itemsize) == (tile, tile)
    assert fa.window_steps(t, tile, tile, window) == (k_steps, q_steps)
    assert k_steps <= math.ceil((window + tile - 1) / tile) + 1 < t // tile
    q = jax.ShapeDtypeStruct((2, 28, t, 128), dtype, sharding=one_chip)

    def grads(q, k, v):
        with jax.default_matmul_precision(precision):
            return jax.grad(
                lambda q, k, v: fa.flash_attention(
                    q, k, v, True, None, None, False, window
                ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    grids = re.findall(r"grid=\((\d+), (\d+), (\d+)\)",
                       str(jax.make_jaxpr(grads)(q, q, q)))
    assert grids == [("56", str(t // tile), str(k_steps)),   # forward
                     ("56", str(t // tile), str(q_steps))], grids  # backward
    text = jax.jit(grads).lower(q, q, q).compile().as_text()
    for name in ("flash_attention_window_fwd",
                 "flash_attention_window_bwd_dq_dkv"):
        assert re.search(rf"%[\w.\-]*{name}[\w.\-]* = ", text), name
    assert not re.search(r"%[\w.\-]*flash_attention_(fwd|bwd)", text)
    assert not re.search(BWD_DKV, text)


def test_windowed_routed_hybridlm_epoch_program_fits_the_chip(
        one_chip, no_compile_cache, kernels_compile):
    """ISSUE 42: the benchmark's epoch program of
    ``smallthinker-21b-a3b.pretrain-16k-window`` (656,529,920 float32
    parameters, AdamW under its warm-up, 2 steps of 2 x 16,384 tokens
    gathered from the resident rows and scanned, parameters and optimizer
    state donated, the steps' report summed) for the described v5e: within
    15.5e9 bytes (14.97e9 here: arguments 7.88e9, all aliased, temporaries
    7.09e9); ONE causal flash forward and ONE fused backward call (the
    global layer) and THREE window calls of each (kept ``attn_out`` and
    ``attn_lse``: none recomputed; PR 43: no dk/dv call of its own); each
    of the four expert layers at the likely bound of 61,440 rows with the
    worst case (196,608) as the overflow's arm."""
    import json
    import os

    from raydp_tpu.estimator.jax_estimator import (
        MODEL_LOSS, _scan_over_batches, make_train_step)
    from raydp_tpu.models import RoutedHybridLM, hybridlm_optimizer

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        config = json.load(f)
    steps, batch, tokens = 2, 2, 16384
    module = RoutedHybridLM.from_config(
        config, **config["model"]["kwargs"])
    assert module.expert_row_bound(batch * tokens) == 196_608
    assert module.expert_likely_row_bound(batch * tokens) == 61_440
    on_chip = functools.partial(_on_chip, one_chip=one_chip)
    rows = on_chip(jax.ShapeDtypeStruct((steps * batch, tokens + 1), jnp.int32))
    perm = on_chip(jax.ShapeDtypeStruct((steps * batch,), jnp.int32))
    params = on_chip(jax.eval_shape(
        lambda r, s: module.init(r, s, None, method="loss"),
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, tokens + 1), jnp.int32)))
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == 656_529_920
    tx = hybridlm_optimizer(**config["model"]["adamw"])
    state = on_chip(jax.eval_shape(tx.init, params))
    step = make_train_step(module, MODEL_LOSS, tx)

    def epoch(params, state, rows, perm):
        return _scan_over_batches(
            step, params, state,
            rows[perm].reshape(steps, batch, tokens + 1), None)

    compiled = jax.jit(epoch, donate_argnums=(0, 1)).lower(
        params, state, rows, perm).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print("windowed routed epoch program holds", held)
    assert held <= 15.5e9, held
    text = compiled.as_text()
    for name, calls in (("flash_attention_fwd", 1),
                        ("flash_attention_bwd_dq_dkv", 1),
                        ("flash_attention_window_fwd", 3),
                        ("flash_attention_window_bwd_dq_dkv", 3)):
        assert len(re.findall(
            rf"%[\w.\-]*{name}[\w.\-]* = ", text)) == calls, name
    assert not re.search(BWD_DKV, text)
    conditionals = re.findall(r"= (\(.*?\)) conditional\(", text)
    assert len(conditionals) == 8
    assert not any("[196608," in result for result in conditionals)
    assert len(re.findall(r"%[\w.\-]*gmm[\w.\-]* = \S+ custom-call", text)) == 64
    assert _loss_products(text, "hybridlm.loss") == 3
    assert "f32[4,16]" in text.split("ENTRY")[1].split("\n")[0]


# -- the delta-rule hybrid's epoch program -----------------------------------------


def test_delta_hybridlm_epoch_program_fits_the_chip(
        one_chip, no_compile_cache, kernels_compile):
    """ISSUE 45: the benchmark's epoch program of
    ``olmo-hybrid-7b.pretrain-8k-delta`` (766,241,946 float32 parameters
    counted from the built tree, AdamW, 3 steps of 1 x 8192 tokens gathered
    from the resident rows and scanned, parameters and optimizer state
    donated) for the described v5e: within 15.5e9 bytes; ONE causal flash
    forward and ONE fused backward call (the full-attention layer, 15 heads
    of 128; ``attn_out`` and ``attn_lse`` kept: none recomputed); the three
    delta-rule layers' triangular solves compile for the chip; three
    products in the loss; every instruction of the scan under the scope
    ``delta_rule`` inside ``hybridlm.delta``."""
    import json
    import os

    from raydp_tpu.estimator.jax_estimator import (
        MODEL_LOSS, _scan_over_batches, make_train_step)
    from raydp_tpu.models import HybridLM, hybridlm_optimizer
    from raydp_tpu.obs import profiler

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    steps, tokens = 3, 8192
    module = HybridLM.from_config(config, **config["model"]["kwargs"])
    assert module.layer_types == ("delta", "delta", "delta", "attention")
    on_chip = functools.partial(_on_chip, one_chip=one_chip)
    rows = on_chip(jax.ShapeDtypeStruct((steps, tokens + 1), jnp.int32))
    perm = on_chip(jax.ShapeDtypeStruct((steps,), jnp.int32))
    params = on_chip(jax.eval_shape(
        lambda r, s: module.init(r, s, None, method="loss"),
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, tokens + 1), jnp.int32)))
    sizes = {name: sum(leaf.size for leaf in jax.tree.leaves(sub))
             for name, sub in params["params"].items()}
    assert sizes == {
        "embed": 12_544 * 3840, "head": 3840 * 12_544, "final_norm": 3840,
        "layer_0": 171_195_102, "layer_1": 171_195_102,
        "layer_2": 171_195_102, "layer_3": 156_314_880}
    assert sum(sizes.values()) == 766_241_946
    tx = hybridlm_optimizer(**config["model"]["adamw"])
    state = on_chip(jax.eval_shape(tx.init, params))
    step = make_train_step(module, MODEL_LOSS, tx)

    def epoch(params, state, rows, perm):
        return _scan_over_batches(
            step, params, state, rows[perm].reshape(steps, 1, tokens + 1), None)

    compiled = jax.jit(epoch, donate_argnums=(0, 1)).lower(
        params, state, rows, perm).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    print("delta hybrid epoch program holds", held)
    assert held <= 15.5e9, held
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert len(re.findall(rf"%[\w.\-]*{name}[\w.\-]* = ", text)) == 1, name
    assert not re.search(BWD_DKV, text)
    assert _loss_products(text, "hybridlm.loss") == 3
    chains = [tuple(said["scopes"])
              for said in profiler.scopes_in_text(text).values()]
    inside = [c for c in chains if "delta_rule" in c]
    assert inside and all("hybridlm.delta" in c for c in inside)
