"""The kernels' small compiles for a described TPU v5e: the flash kernels at
every LM cell's widths (the forward and the one-call backward, bf16 and the
matched check's float32), heads of 64, the grouped products of the routed
cells, the channel-decay delta rule's two kernels (``tpu_compile_helpers``
says how and why)."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from tpu_compile_helpers import (  # noqa: F401 - fixtures by name
    BWD_DKV, kernels_compile, mosaic_grids, no_compile_cache, one_chip)


@pytest.mark.parametrize("dtype, precision, tile, live", [
    (jnp.bfloat16, None, 1024, 10), (jnp.float32, "highest", 512, 36)])
def test_flash_forward_and_backward_compile_at_ouro_widths(
        one_chip, no_compile_cache, mosaic_grids, dtype, precision, tile,
        live):
    """[2 x 16 heads, T 4096, head 128], causal: the timed bf16 step's tiles,
    and the float32 ones of the benchmark's matched check (1024-row float32
    tiles ask the backward kernel for 19.5 MB of its 16 MB of VMEM). ISSUE
    54: both calls step over the tiles under the diagonal alone (10 of 4 x
    4, 36 of 8 x 8)."""
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
    assert mosaic_grids == [("flash_attention_fwd", (32, live)),
                            ("flash_attention_bwd_dq_dkv", (32, live))]


# the fused backward's grid a head: (bf16, float32) tiles under the diagonal,
# or the window's (k-blocks, q-steps)
@pytest.mark.parametrize("heads, t, head, window, steps", [
    (32, 4096, 128, None, (10, 36)), (32, 8192, 64, None, (36, 136)),
    (128, 8192, 64, None, (36, 136)), (56, 16384, 128, None, (136, 528)),
    (56, 16384, 128, 4096, ((16, 5), (32, 9))),
    (15, 8192, 128, None, (36, 136))],
    ids=["ouro", "granite", "routed", "window_cell_global",
         "window_cell_window", "olmo"])
@pytest.mark.parametrize("dtype, precision", [
    (jnp.bfloat16, None), (jnp.float32, "highest")],
    ids=["bf16", "float32_matched"])
def test_fused_flash_backward_compiles_at_the_cells_shapes(
        one_chip, no_compile_cache, mosaic_grids, heads, t, head, window,
        steps, dtype, precision):
    """PR 43: the ONE-call backward pass alone, [batch x heads, T, head] of
    the LM cells (the timed bf16 step and the matched check's float32
    tiles; the Ling and GLM cells' widths: their own files): a head's
    float32 dq lives in VMEM (2-8 MB) beside the tile, so
    the call asks for more than a Mosaic call's 16 MB by
    ``vmem_limit_bytes`` (``fused_vmem_bytes``, from the shapes): what it
    asks for must cover what the compiler needs, here and not on the chip.
    ISSUE 54: the grid Mosaic is handed is (heads, tiles under the
    diagonal), 136 where the 16 x 16 rectangle had 256; a window's stays
    (heads, k-blocks, the q-blocks a k-block's window touches). A dead step
    coming back fails here."""
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
    live = steps[dtype == jnp.float32]
    assert mosaic_grids == [
        (name, (heads, *live) if window else (heads, live))]
    if not window:
        assert (fa.causal_grid(t, t, block, block),
                fa.causal_steps(t, block, block)) == (
                    "live", ((t // block) ** 2, live))


@pytest.mark.parametrize("heads, t, head, steps", [
    (128, 8192, 64, (36, 136)), (56, 16384, 128, (136, 528)),
    (15, 8192, 128, (36, 136))],
    ids=["routed", "window_cell_global", "olmo"])
@pytest.mark.parametrize("dtype, precision", [
    (jnp.bfloat16, None), (jnp.float32, "highest")],
    ids=["bf16", "float32_matched"])
def test_causal_flash_forward_steps_over_live_tiles_at_the_cells_shapes(
        one_chip, no_compile_cache, mosaic_grids, heads, t, head, steps,
        dtype, precision):
    """ISSUE 54: the forward call alone at the cells' shapes no other test
    compiles it at (Ouro and Granite: above and below; Ling and GLM: their
    own files), for the described v5e, on the grid of the tiles under the
    diagonal, its two tables scalar-prefetched."""
    fa = importlib.import_module("raydp_tpu.ops.flash_attention")
    q = jax.ShapeDtypeStruct((1, heads, t, head), dtype, sharding=one_chip)

    def forward(q, k, v):
        with jax.default_matmul_precision(precision):
            return fa.flash_attention(q, k, v, True, None, None, False)

    text = jax.jit(forward).lower(q, q, q).compile().as_text()
    assert text.count("tpu_custom_call") == 1
    assert mosaic_grids == [
        ("flash_attention_fwd", (heads, steps[dtype == jnp.float32]))]


@pytest.mark.parametrize("matched", [False, True], ids=["bf16", "float32_matched"])
def test_flash_kernels_compile_at_head_dim_64(
        one_chip, no_compile_cache, mosaic_grids, matched):
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
    # ISSUE 54: the tiles under the diagonal of 8 x 8 (16 x 16 in float32)
    live = 136 if matched else 36
    assert mosaic_grids == [("flash_attention_fwd", (32, live)),
                            ("flash_attention_bwd_dq_dkv", (32, live))]


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


@pytest.mark.parametrize("dtype, precision", [
    (jnp.bfloat16, None), (jnp.float32, "highest")],
    ids=["bf16", "float32_matched"])
def test_channel_delta_rule_kernels_compile_at_the_ling_cell_shape(
        one_chip, no_compile_cache, kernels_compile, dtype, precision):
    """ISSUE 50: [1, 8192 tokens, 32 heads, 128] keys and values, the decay
    [.., 128] and beta float32: the forward call and the backward call of
    ``ops.delta_rule.channel_gated_delta_rule`` go through Mosaic in the
    timed step's bf16 and in the matched check's float32 (products at
    ``highest``), ONE call each (a gradient alone holds no forward call:
    the backward keeps only the operands), within the VMEM the flash
    kernels may ask for, on the model's own [T, H * 128] layout: no
    ``[.., c, d]`` head-major copy, no triangular solve, no loop."""
    fa = importlib.import_module("raydp_tpu.ops.flash_attention")
    from raydp_tpu.ops import delta_rule

    t, h, d = 8192, 32, 128
    assert (delta_rule.vmem_bytes(t, d, d, backward=False)
            < delta_rule.vmem_bytes(t, d, d) <= fa.VMEM_ASK_BOUND_BYTES)
    x = jax.ShapeDtypeStruct((1, t, h, d), dtype, sharding=one_chip)
    decay = jax.ShapeDtypeStruct((1, t, h, d), jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((1, t, h), jnp.float32, sharding=one_chip)

    def forward(q, k, v, log_alpha, beta):
        with jax.default_matmul_precision(precision):
            return delta_rule.channel_gated_delta_rule(
                q, k, v, log_alpha, beta)

    def grads(*operands):
        return jax.grad(lambda *a: forward(*a).astype(jnp.float32).sum(),
                        argnums=(0, 1, 2, 3, 4))(*operands)

    for f, here, gone in ((forward, "delta_rule_fwd", "delta_rule_bwd"),
                          (grads, "delta_rule_bwd", "delta_rule_fwd")):
        text = jax.jit(f).lower(x, x, x, decay, beta).compile().as_text()
        assert len(re.findall(rf"%[\w.\-]*{here}[\w.\-]* = ", text)) == 1
        assert not re.search(rf"%[\w.\-]*{gone}[\w.\-]* = ", text)
        assert text.count("tpu_custom_call") == 1
        assert "triangular" not in text and " while(" not in text
    shapes = [(leaf.shape, leaf.dtype) for leaf in jax.tree.leaves(
        jax.eval_shape(grads, x, x, x, decay, beta))]
    assert shapes == [((1, t, h, d), dtype)] * 3 + [
        ((1, t, h, d), jnp.float32), ((1, t, h), jnp.float32)]


@pytest.mark.parametrize("t, d, devices, why", [
    (8192, 96, 1, "whole 128-lane tiles"), (8192, 128, 4, "no mesh"),
    (32768, 128, 1, "every chunk's inverse")],
    ids=["heads_of_96", "devices_and_no_mesh", "vmem_at_32k"])
def test_channel_delta_rule_refuses_what_mosaic_cannot_take(
        kernels_compile, monkeypatch, t, d, devices, why):
    """What the interpreter bears and the chip does not is refused by the op
    with its cause, not inside Mosaic or XLA's partitioner: heads that are
    not whole lane tiles, several devices with no mesh, a sequence whose
    kept inverses and states pass the VMEM a call may ask for."""
    from raydp_tpu.ops import delta_rule

    monkeypatch.setattr(jax, "device_count", lambda *_: devices)
    x = jax.ShapeDtypeStruct((1, t, 4, d), jnp.bfloat16)
    decay = jax.ShapeDtypeStruct((1, t, 4, d), jnp.float32)
    beta = jax.ShapeDtypeStruct((1, t, 4), jnp.float32)
    with pytest.raises(ValueError, match=why):
        jax.eval_shape(delta_rule.channel_gated_delta_rule,
                       x, x, x, decay, beta)


@pytest.mark.parametrize("dtype, precision", [
    (jnp.bfloat16, None), (jnp.float32, "highest")],
    ids=["bf16", "float32_matched"])
def test_kda_mixer_kernels_compile_at_the_ling_cell_shape(
        one_chip, no_compile_cache, kernels_compile, dtype, precision):
    """ISSUE 52: the four calls of ``ops.kda_mixer`` at [1, 8192 tokens, 32
    heads x 128] in the timed step's bf16 and the matched check's float32,
    the scan's two calls between them on the same flat layout: FIVE Mosaic
    calls in a gradient (the read-out's forward call makes nothing a
    backward pass reads) and no ``[T, H, 128]`` view between any two (no
    copy, no transpose of a mixer's channels); what the kernels refuse at
    such sizes is what the scan refuses."""
    from raydp_tpu.ops import delta_rule, kda_mixer

    t, h, d = 8192, 32, 128
    assert kda_mixer.refused(t, 4, d, d) is None
    assert "whole 128-lane tiles" in kda_mixer.refused(t, 4, 96, 192)
    wide = jax.ShapeDtypeStruct((1, t, h * d), dtype, sharding=one_chip)

    def f32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    def mixer(pq, pk, pv, pf, conv_w, a_log, dt_bias, beta, gate, gain):
        with jax.default_matmul_precision(precision):
            o = delta_rule.channel_gated_delta_rule(*kda_mixer.operands(
                pq, pk, pv, pf, conv_w, a_log, dt_bias, -5.0), beta)
            return kda_mixer.read_out(o, gate, gain, 1e-6)

    def grads(*given):
        return jax.grad(lambda *a: mixer(*a).astype(jnp.float32).sum(),
                        argnums=tuple(range(10)))(*given)

    given = (wide, wide, wide, wide, f32(4, 3 * h * d), f32(h), f32(h * d),
             f32(1, t, h), f32(1, t, h), f32(d))
    text = jax.jit(grads).lower(*given).compile().as_text()
    for name in ("kda_operands_fwd", "kda_operands_bwd", "delta_rule_fwd",
                 "delta_rule_bwd", "kda_read_out_bwd"):
        assert len(re.findall(rf"%[\w.\-]*{name}[\w.\-]* = ", text)) == 1, name
    assert text.count("tpu_custom_call") == 5
    assert f"[{t},{h},{d}]" not in text and f"[{t // 8},8,{h},{d}]" not in text
    assert " transpose(" not in text
    got = jax.eval_shape(grads, *given)
    assert [(g.shape, g.dtype) for g in got] == [
        (x.shape, x.dtype) for x in given]
