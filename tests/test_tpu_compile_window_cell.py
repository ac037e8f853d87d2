"""The window cell (``smallthinker-21b-a3b.pretrain-16k-window``) compiled for a
described TPU v5e: the window kernels' grids at its widths, and its epoch
program (``tpu_compile_helpers`` says how and why)."""

import importlib
import math
import re

import jax
import jax.numpy as jnp
import pytest
from tpu_compile_helpers import (  # noqa: F401 - fixtures by name
    BWD_DKV, calls, cell_config, epoch_program, kernels_compile,
    loss_products, no_compile_cache, one_chip)


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
    from raydp_tpu.models import RoutedHybridLM, hybridlm_optimizer

    config = cell_config("smallthinker-21b-a3b")
    batch, tokens = 2, 16384
    module = RoutedHybridLM.from_config(
        config, **config["model"]["kwargs"])
    assert module.expert_row_bound(batch * tokens) == 196_608
    assert module.expert_likely_row_bound(batch * tokens) == 61_440
    params, compiled, held = epoch_program(
        module, hybridlm_optimizer(**config["model"]["adamw"]), 2, batch,
        tokens, one_chip)
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == 656_529_920
    print("windowed routed epoch program holds", held)
    assert held <= 15.5e9, held
    text = compiled.as_text()
    for name, count in (("flash_attention_fwd", 1),
                        ("flash_attention_bwd_dq_dkv", 1),
                        ("flash_attention_window_fwd", 3),
                        ("flash_attention_window_bwd_dq_dkv", 3)):
        assert calls(text, name) == count, name
    assert not re.search(BWD_DKV, text)
    conditionals = re.findall(r"= (\(.*?\)) conditional\(", text)
    assert len(conditionals) == 8
    assert not any("[196608," in result for result in conditionals)
    assert len(re.findall(r"%[\w.\-]*gmm[\w.\-]* = \S+ custom-call", text)) == 64
    assert loss_products(text, "hybridlm.loss") == 3
    assert "f32[4,16]" in text.split("ENTRY")[1].split("\n")[0]
