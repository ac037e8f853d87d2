"""The latent-attention / multi-token-prediction cell
(``glm-4.7-flash.pretrain-8k-mtp``) compiled for a described TPU v5e: the
flash kernels at keys of 256 over values of 256, and its epoch program
(``tpu_compile_helpers`` says how and why)."""

import importlib
import re

import jax
import jax.numpy as jnp
import pytest
from tpu_compile_helpers import (  # noqa: F401 - fixtures by name
    BWD_DKV, calls, cell_config, epoch_program, instructions,
    kernels_compile, loss_products, mosaic_grids, no_compile_cache, one_chip)


@pytest.mark.parametrize("dtype, precision, tile, live", [
    (jnp.bfloat16, None, 512, 136), (jnp.float32, "highest", 256, 528)])
def test_flash_kernels_compile_at_keys_of_256_over_values_of_256(
        one_chip, no_compile_cache, mosaic_grids, dtype, precision, tile,
        live):
    """[20 heads, T 8192], q, k, v, o and do of 256 lanes: the timed bf16
    step's tiles and the float32 ones of the matched check. A tile's bytes go
    by the two widths' lanes, 256 + 256: half the rows of heads of 128; the
    backward is the one fused call, whose dq [8192, 256] float32 (8.4 MB)
    fits the VMEM it may ask for beside the tiles. ISSUE 54: both calls step
    over the 136 tiles under the diagonal of 16 x 16 (528 of 32 x 32 in
    float32), not over the rectangle."""
    fa = importlib.import_module("raydp_tpu.ops.flash_attention")
    t, itemsize = 8192, jnp.dtype(dtype).itemsize
    assert fa.pick_blocks(t, t, head_dim=256, itemsize=itemsize,
                          value_dim=256) == (tile, tile)
    assert fa.backward_form(t, t, 256, itemsize, value_dim=256) == "fused"
    assert fa.dq_resident_bytes(t, 256) == 8 * 2**20
    assert fa.fused_vmem_bytes(t, 256, tile, itemsize, 256) <= (
        fa.VMEM_ASK_BOUND_BYTES)
    q = jax.ShapeDtypeStruct((1, 20, t, 256), dtype, sharding=one_chip)

    def grads(q, k, v):
        with jax.default_matmul_precision(precision):
            return jax.grad(
                lambda q, k, v: fa.flash_attention(
                    q, k, v, True, None, None, False
                ).astype(jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = jax.jit(grads).lower(q, q, q).compile().as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert calls(text, name) == 1, name
    assert not re.search(BWD_DKV, text)
    assert mosaic_grids == [("flash_attention_fwd", (20, live)),
                            ("flash_attention_bwd_dq_dkv", (20, live))]


def test_latent_mtp_hybridlm_epoch_program_fits_the_chip(
        one_chip, no_compile_cache, kernels_compile, mosaic_grids):
    """ISSUE 53: the benchmark's epoch program of
    ``glm-4.7-flash.pretrain-8k-mtp`` (706,518,848 float32 parameters counted
    from the built tree: the stage of the published model, name by name;
    AdamW under its warm-up with the balancing rule, 3 steps of 1 x 8192
    tokens gathered from the resident rows and scanned, parameters and
    optimizer state donated, the steps' report summed) for the described
    v5e: under the suite's ceiling for a cell's program; SIX causal flash
    forward and SIX fused backward calls (five layers and the module's block;
    kept ``attn_out`` and ``attn_lse``: none recomputed); the module under
    ``hybridlm.mtp`` with its combine and its loss inside, its block under
    the scopes every block has; three products in EACH loss; five expert
    layers at the likely bound with the worst case (32,768 rows) as the
    overflow's arm."""
    from raydp_tpu.models import LatentMTPHybridLM, hybridlm_optimizer
    from raydp_tpu.obs import profiler

    config = cell_config("glm-4.7-flash")
    module = LatentMTPHybridLM.from_config(config, **config["model"]["kwargs"])
    assert module.layer_types == ("mla",) * 5
    assert module.ffn_kinds == ("dense",) + ("experts",) * 4
    assert module.mtp_built and module.mtp_weight == 0.3
    assert module.expert_layers == 5
    assert module.expert_row_bound(8192) == 32_768
    assert module.expert_likely_row_bound(8192) == LIKELY_ROWS
    params, compiled, held = epoch_program(
        module, hybridlm_optimizer(**config["model"]["adamw"]), 3, 1, 8192,
        one_chip)
    sizes = {name: sum(leaf.size for leaf in jax.tree.leaves(sub))
             for name, sub in params["params"].items()}
    assert sizes == {
        "embed": 19_360 * 2048, "head": 2048 * 19_360, "final_norm": 2048,
        "layer_0": 84_677_888, "layer_1": 106_829_120,
        "layer_2": 106_829_120, "layer_3": 106_829_120,
        "layer_4": 106_829_120, "mtp_0": 115_223_872}
    assert sum(sizes.values()) == 706_518_848
    shapes = {k: v.shape for k, v in params["params"]["mtp_0"].items()}
    assert shapes == {
        "wqa": (2048, 768), "q_norm": (768,), "wqb": (768, 20 * 256),
        "wkva": (2048, 512 + 64), "kv_norm": (512,),
        "wkvb": (512, 20 * (192 + 256)), "wo": (20 * 256, 2048),
        "norm1": (2048,), "norm2": (2048,), "router": (2048, 64),
        "expert_bias": (64,), "w13": (8, 2048, 2 * 1536),
        "w2": (8, 1536, 2048), "shared_in": (2048, 2 * 1536),
        "shared_out": (1536, 2048), "eh_proj": (4096, 2048),
        "enorm": (2048,), "hnorm": (2048,), "final_norm": (2048,)}
    mixer = sum(params["params"]["layer_1"][name].size for name in (
        "wqa", "q_norm", "wqb", "wkva", "kv_norm", "wkvb", "wo"))
    assert mixer == 21_759_232
    print("latent mtp hybrid epoch program holds", held,
          "instructions", instructions(compiled.as_text()))
    # 11.78e9 as first compiled (the Ling cell: 13.50e9); the suite's ceiling for
    # a cell's program is 15.5e9
    assert held <= HELD_CEILING, held
    text = compiled.as_text()
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq_dkv"):
        assert calls(text, name) == 6, (name, calls(text, name))
    # ISSUE 54: every causal call of the program steps over the 136 tiles
    # under the diagonal of a head's 16 x 16, where the rectangle had 256
    flash = [grid for name, grid in mosaic_grids if "flash_attention" in name]
    assert flash and set(flash) == {(20, 136)}, flash
    assert not re.search(BWD_DKV, text)
    assert loss_products(text, "hybridlm.loss") == 3
    assert loss_products(text, "hybridlm.mtp.loss") == 3
    said = profiler.scopes_in_text(text)
    chains = [tuple(v["scopes"]) for v in said.values()]
    for scope in ("hybridlm.mtp", "hybridlm.mtp.combine", "hybridlm.mtp.loss",
                  "hybridlm.attention.query", "hybridlm.attention.latent",
                  "hybridlm.experts.shared", "hybridlm.experts.route",
                  "hybridlm.experts.gmm"):
        assert any(scope in c for c in chains), scope
    # the module's parts lie inside it; its block is under the blocks' scopes
    assert all("hybridlm.mtp" in c for c in chains
               if "hybridlm.mtp.combine" in c or "hybridlm.mtp.loss" in c)
    inside = [c for c in chains if "hybridlm.mtp" in c]
    for scope in ("hybridlm.attention", "hybridlm.experts"):
        assert any(scope in c for c in inside), scope
    # the main head's loss is not the module's, nor the other way round
    assert not any("hybridlm.loss" in c for c in inside)
    flash = {name: tuple(v["scopes"]) for name, v in said.items()
             if "flash_attention_fwd" in name}
    assert sum("hybridlm.mtp" in c for c in flash.values()) == 1, flash


# rows an expert layer runs at wherever the load fits them: at a share of 1/8
# ops.experts.likely_row_bound widens SLACK's margin of 0.25 by (1/4 x 8)^1/2
# = 1.41: 1.354 x the even share (8192 x 4 x 8 / 64 = 4096 pairs), in tiles
LIKELY_ROWS = 5632
HELD_CEILING = 12.2e9
