"""The gradients of ``LoopLM`` (the Ouro cell, at the published widths) and
``TransformerLM`` compiled for a described TPU v5e: what a recomputed block
keeps, what it holds, how many instructions it is (``tpu_compile_helpers`` says
how and why). The two ``LoopLM`` cases are four whole-program compiles, two
minutes each here: the first to thin if the suite's clock tightens again."""

import re

import jax
import jax.numpy as jnp
import pytest
from tpu_compile_helpers import (  # noqa: F401 - fixtures by name
    FLASH_FWD, instructions, kernels_compile, loss_products, no_compile_cache,
    on_chip, one_chip)

OURO = dict(vocab_size=49152, attn_impl="flash")  # LoopLM's defaults are the rest


def _looplm_gradient(one_chip, rows, tokens, matched=False, **kw):
    """The gradient of ``LoopLM.loss`` compiled for the described chip: the
    timed step's bf16 form, or the float32 / highest / ``with_states`` form
    of the benchmark's ``matched`` check."""
    from raydp_tpu.models import LoopLM

    module = LoopLM(**OURO, dtype=jnp.float32 if matched else jnp.bfloat16, **kw)
    x = jax.ShapeDtypeStruct((rows, tokens + 1), jnp.int32, sharding=one_chip)
    params = on_chip(jax.eval_shape(
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
    assert loss_products(kept.as_text(), "looplm.exit_loss") == 3
    # what a change that leaves the model's options alone must not move
    # (12,395 until PR 43 made the flash backward one call of two; 12,341
    # until ISSUE 54 handed the flash calls their tables)
    if not matched:
        assert instructions(kept.as_text()) == 12_400


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
    params = on_chip(jax.eval_shape(module.init, jax.random.PRNGKey(0), x),
                      one_chip)
    text = jax.jit(jax.grad(
        lambda p, x: module.apply(p, x).astype(jnp.float32).sum()
    )).lower(params, x).compile().as_text()
    assert text.count("tpu_custom_call") == calls_a_layer * layers
    assert len(re.findall(FLASH_FWD, text)) == (2 if remat else 1) * layers
