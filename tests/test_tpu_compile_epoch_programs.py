"""The epoch programs of the Granite, routed (LFM2) and delta-rule (Olmo) cells
at the published widths, compiled for a described TPU v5e: what each holds of
the chip's memory, its Mosaic calls, its loss's products, how many
instructions it is (``tpu_compile_helpers`` says how and why; the window cell's
is ``test_tpu_compile_window_cell.py``)."""

import collections
import re

import jax
from tpu_compile_helpers import (  # noqa: F401 - fixtures by name
    BWD_DKV, calls, cell_config, epoch_program, instructions, kernels_compile,
    loss_products, no_compile_cache, one_chip)


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
    from raydp_tpu.models import HybridLM, hybridlm_optimizer

    module = HybridLM(vocab_size=50176, attn_impl="flash")  # published widths
    params, compiled, held = epoch_program(
        module, hybridlm_optimizer(), 3, 1, 8192, one_chip)
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == 849_230_784
    assert held <= 15.5e9, held
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert calls(text, name) == 1, name
    assert not re.search(BWD_DKV, text)
    assert loss_products(text, "hybridlm.loss") == 3
    # what a change that leaves the model's options alone must not move
    # (30,285 until PR 43 made the flash backward one call of two; 30,162
    # until ISSUE 54 handed the two flash calls their tables: two int32
    # constants a call and what the compiler schedules around them)
    assert instructions(text) == 30_178


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
    from raydp_tpu.models import RoutedHybridLM, hybridlm_optimizer

    config = cell_config("lfm2-8b-a1b")
    batch, tokens = 4, 8192
    module = RoutedHybridLM.from_config(config, **config["model"]["kwargs"])
    worst = module.expert_row_bound(batch * tokens)
    assert worst == batch * tokens * 4
    assert module.expert_likely_row_bound(batch * tokens) < worst
    # AdamW under the warm-up, the balancing rule on the biases
    params, compiled, held = epoch_program(
        module, hybridlm_optimizer(**config["model"]["adamw"]), 3, batch,
        tokens, one_chip)
    assert sum(leaf.size for leaf in jax.tree.leaves(params)) == 507_820_288
    print("routed epoch program holds", held)
    # the parent's program, the worst case alone, held 13,006,128,128
    assert held <= 13.05e9, held
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert calls(text, name) == 1, name
    assert not re.search(BWD_DKV, text)
    conditionals = re.findall(r"= (\(.*?\)) conditional\(", text)
    assert len(conditionals) == 8
    assert not any(f"[{worst}," in result for result in conditionals)
    assert len(re.findall(r"%[\w.\-]*gmm[\w.\-]* = \S+ custom-call", text)) == 64
    assert loss_products(text, "hybridlm.loss") == 3
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
    # Mosaic call fewer, and the compiler schedules 199 instructions more;
    # 31,223 until ISSUE 54 handed the two flash calls their tables)
    assert instructions(text) == 31_239


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
    from raydp_tpu.models import HybridLM, hybridlm_optimizer
    from raydp_tpu.obs import profiler

    config = cell_config("olmo-hybrid-7b")
    module = HybridLM.from_config(config, **config["model"]["kwargs"])
    assert module.layer_types == ("delta", "delta", "delta", "attention")
    params, compiled, held = epoch_program(
        module, hybridlm_optimizer(**config["model"]["adamw"]), 3, 1, 8192,
        one_chip)
    sizes = {name: sum(leaf.size for leaf in jax.tree.leaves(sub))
             for name, sub in params["params"].items()}
    assert sizes == {
        "embed": 12_544 * 3840, "head": 3840 * 12_544, "final_norm": 3840,
        "layer_0": 171_195_102, "layer_1": 171_195_102,
        "layer_2": 171_195_102, "layer_3": 156_314_880}
    assert sum(sizes.values()) == 766_241_946
    print("delta hybrid epoch program holds", held)
    assert held <= 15.5e9, held
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert calls(text, name) == 1, name
    assert not re.search(BWD_DKV, text)
    assert loss_products(text, "hybridlm.loss") == 3
    chains = [tuple(said["scopes"])
              for said in profiler.scopes_in_text(text).values()]
    inside = [c for c in chains if "delta_rule" in c]
    assert inside and all("hybridlm.delta" in c for c in inside)
