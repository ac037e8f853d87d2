"""``HybridLM`` of the ``bailing_hybrid`` family (Ling-3.0-flash: KDA layers,
a latent-attention layer, group-limited routed experts beside a shared one)
against the benchmark's plain reference (``benchmark/reference/ling_hybrid``),
float32, at the configuration's rehearsal sizes: loss, logits, every
parameter's gradient and the selection; the share test; ``from_config``;
``fit_facts``. The mutations the comparison must catch are
``test_ling_mutations.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import ling_hybrid_model as lm
from ling_hybrid_model import no_persistent_cache  # noqa: F401 - autouse
from ling_hybrid_model import MATCHED, T, V, model
from ling_hybrid_model import config as _config
from benchmark.reference import ling_hybrid as ref
from raydp_tpu.models import HybridLM, LatentDeltaHybridLM
from raydp_tpu.ops import experts as experts_op


@pytest.fixture(scope="module")
def batch():
    return lm.batch()


def test_system_against_the_reference(batch):
    """Loss, logits, every gradient and every choice, float32, of the six
    recomputed layers as the cell builds them: the chunked channel-decay
    scan (two sub-blocks of 16 here) against the reference's recurrence
    token by token; latent attention through the flash kernels at keys of
    24 over values of 16; the shared expert; the group-limited top-2 of 16.
    (Two layers with full attention, not recomputed:
    ``test_ling_mutations.py``'s first case.)"""
    m = model(attn_impl="flash", remat=True)
    assert m.layer_types == ("kda", "kda", "kda", "kda", "mla", "kda")
    assert m.ffn_kinds == ("dense",) + ("experts",) * 5
    p = lm.params(m, batch)
    got = lm.gaps(lm.program(m, p, batch), p, batch, ref.config_of(lm.CONFIG))
    assert all(g <= limit for g, limit in zip(got, MATCHED)), got


def test_the_shares_routed_parts_and_the_shared_expert_once_are_the_uncut_layer():
    """The guide's share test (model-configs, section 4): the 8 shares of 2
    experts each of a 16-expert layer, routed by the one group-limited
    selection, add up with the shared expert ONCE to the uncut reference's
    layer (every expert held, the shared one inside)."""
    keys = jax.random.split(jax.random.PRNGKey(3), 7)
    d, f, total, held, k, n = 32, 16, 16, 2, 2, 64
    u = jax.random.normal(keys[0], (n, d))
    w = {"router": jax.random.normal(keys[1], (d, total)),
         "expert_bias": 0.05 * jax.random.normal(keys[2], (total,)),
         "w13": 0.2 * jax.random.normal(keys[3], (total, d, 2 * f)),
         "w2": 0.2 * jax.random.normal(keys[4], (total, f, d)),
         "shared_in": 0.2 * jax.random.normal(keys[5], (d, 2 * f)),
         "shared_out": 0.2 * jax.random.normal(keys[6], (f, d))}
    cfg = {"num_experts_per_tok": k, "n_group": 4, "topk_group": 2,
           "routed_scaling_factor": 2.5, "first_expert": 0}
    with jax.default_matmul_precision("highest"):
        uncut, free, _, _ = ref._experts(w, u[None], cfg, None, False)
        shared = ref._swiglu(u, w["shared_in"], w["shared_out"])
        parts, chosen = [], []
        for first in range(0, total, held):
            out, report = experts_op.routed_experts(
                u, w["router"], w["expert_bias"],
                w["w13"][first:first + held], w["w2"][first:first + held],
                first=first, top_k=k, scaling=2.5, groups=4, groups_kept=2,
                weight_eps=1e-20)
            assert float(report["dropped"]) == 0
            parts.append(out)
            chosen.append(np.asarray(report["sel"]))
    assert all((c == chosen[0]).all() for c in chosen)
    assert (np.sort(chosen[0], -1) == np.sort(np.asarray(free[0]), -1)).all()
    # a token's choices lie in two of the four groups of four
    assert all(len({int(e) // 4 for e in row}) <= 2 for row in chosen[0])
    total_out = sum(parts) + shared
    assert float(jnp.abs(total_out - uncut[0]).max()) <= 1e-5 * float(
        jnp.abs(uncut).max())
    # one share alone, or the shared expert a share, is not the layer
    assert float(jnp.abs(sum(parts) + 8 * shared - uncut[0]).max()) > 1e-2


def test_the_group_limit_keeps_a_choice_inside_the_best_groups():
    scores = jnp.asarray([[0.9, 0.1, 0.1, 0.1, 0.5, 0.45, 0.1, 0.1,
                           0.6, 0.0, 0.0, 0.0, 0.3, 0.3, 0.3, 0.3]])
    logits = jnp.log(scores + 1e-9) - jnp.log1p(-scores + 1e-9)
    eye = jnp.eye(16)
    free, _ = experts_op.route(logits, eye, jnp.zeros(16), 3)
    limited, w = experts_op.route(logits, eye, jnp.zeros(16), 3, groups=4,
                                  groups_kept=1, weight_eps=1e-20)
    # group scores (the sums of the two largest): 1.0, 0.95, 0.6, 0.6
    assert sorted(np.asarray(free)[0]) == [0, 4, 8]
    assert sorted(np.asarray(limited)[0]) == [0, 1, 2]
    assert abs(float(w.sum()) - 1.0) < 1e-5
    with pytest.raises(ValueError, match="groups"):
        experts_op.route(logits, eye, jnp.zeros(16), 3, groups=5,
                         groups_kept=1)


def test_the_parameter_tree_is_the_stage_of_the_published_model(batch):
    """The leaves a layer holds at the rehearsal's widths, by the published
    equations (the count at the PUBLISHED widths, 714,989,856, is
    ``tests/test_tpu_compile_ling_cell.py``'s)."""
    m = model(LatentDeltaHybridLM)
    shapes = jax.eval_shape(
        lambda r: m.init(r, batch, None, method="loss"),
        jax.random.PRNGKey(0))["params"]
    d, heads, dk = 64, 2, 16
    assert {k: v.shape for k, v in shapes["layer_0"].items()} == {
        "wq": (d, heads * dk), "wk": (d, heads * dk), "wv": (d, heads * dk),
        "wf": (d, heads * dk), "wb": (d, heads), "wg": (d, heads),
        "wo": (heads * dk, d), "conv_w": (4, 3 * heads * dk),
        "A_log": (heads,), "dt_bias": (heads * dk,), "gate_norm": (dk,),
        "norm1": (d,), "norm2": (d,), "w_in": (d, 2 * 96), "w_out": (96, d)}
    mla = {k: v.shape for k, v in shapes["layer_4"].items()}
    assert mla == {
        "wq": (d, heads * 24), "wkva": (d, 32 + 8), "kv_norm": (32,),
        "wkvb": (32, heads * (16 + 16)), "wg": (d, heads),
        "wo": (heads * 16, d), "norm1": (d,), "norm2": (d,),
        "router": (d, 16), "expert_bias": (16,), "w13": (2, d, 64),
        "w2": (2, 32, d), "shared_in": (d, 64), "shared_out": (32, d)}
    assert shapes["head"].shape == (d, V) and shapes["embed"].shape == (V, d)


@pytest.mark.parametrize("change, match", [
    ({"mtp_loss_scaling_factor": 0.3}, "multi-token-prediction"),
    ({"use_nGPT": True}, "use_nGPT"),
    ({"value_norm": True}, "value_norm"),
    ({"kda_safe_gate": False}, "kda_safe_gate"),
    ({"use_kda_lora": True}, "use_kda_lora"),
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"moe_shared_expert_intermediate_size": 48},
     "moe_shared_expert_intermediate_size"),
    ({"rope_interleave": False}, "rope_interleave"),
    ({"gated_attention_proj_granularity_type": "element_wise"},
     "gated_attention_proj_granularity_type"),
    ({"share": {"first_layer": 34, "experts_total": 16}}, "SwiGLU clamp"),
])
def test_from_config_refuses_what_the_family_does_not_build(change, match):
    with pytest.raises(ValueError, match=match):
        HybridLM.from_config(_config(**change))


def test_a_decay_floor_the_scan_cannot_bear_is_refused(batch):
    """A sub-block's operands are decayed from its middle row: 8 tokens at
    the floor are an exponent float32 must hold."""
    from raydp_tpu.ops import delta_rule

    assert delta_rule.LOG_DECAY_FLOOR == -10.0
    m = model(kda_decay_floor=-12.0)
    with pytest.raises(ValueError, match="kda_decay_floor -12.0"):
        jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), batch, None,
                                      method="loss"))


def test_the_familys_fields_follow_the_config():
    m = model()
    assert (m.num_heads, m.head_dim, m.rope_head_dim, m.value_width,
            m.latent_rank) == (2, 24, 8, 16, 32)
    assert (m.delta_heads, m.delta_key_dim, m.delta_value_dim,
            m.delta_conv, m.kda_decay_floor) == (2, 16, 16, 4, -5.0)
    assert (m.experts_held, m.experts_total, m.first_expert,
            m.experts_per_token, m.shared_experts, m.expert_groups,
            m.expert_groups_kept, m.routed_scaling) == (
                2, 16, 2, 2, 1, 4, 2, 2.5)
    assert m.expert_weight_eps == 1e-20 and not m.tied_head
    # a pipeline stage further on: layers 6..11 are again 5 KDA to 1 MLA,
    # with no dense layer among them
    later = HybridLM.from_config(_config(
        first_k_dense_replace=0, share={"first_layer": 6,
                                        "experts_total": 16}))
    assert later.layer_types == ("kda",) * 5 + ("mla",)
    assert later.ffn_kinds == ("experts",) * 6


def test_fit_facts_say_what_a_row_holds(batch):
    m = model(attn_impl="flash", dtype=jnp.bfloat16)
    facts = m.fit_facts(batch)
    assert facts["layer_kinds"] == "kda,kda,kda,kda,mla,kda"
    want = {
        "layer_kinds.kda": 5, "layer_kinds.mla": 1, "delta.decay": "channel",
        "delta.scan": "kernel",
        "delta.heads_held": 2, "delta.heads_total": 2, "delta.chunk": 32,
        "delta.state_bytes_per_row": 5 * 4 * 2 * 16 * 16,
        "attention.latent_rank": 32, "attention.key_width": 24,
        "attention.value_width": 16, "attention_backward": "latent=fused",
        "attention.backward_fused_layers": 1,
        "experts.held": 2, "experts.total": 16, "experts.per_token": 2,
        "experts.layers": 5, "experts.shared": 1, "experts.groups": 4,
        "experts.groups_kept": 2}
    assert {k: facts[k] for k in want} == want
    assert facts["delta.flops_per_row"] == 3 * 5 * 6 * 16 * 16 * 2 * T
    assert "delta_out" in facts["remat_keeps"].split(",")
    # the attention's FLOPs count keys and values at their own widths
    parts = m.flops_per_row_parts(T)
    assert parts["attention"] == 6 * 2 * (24 + 16) * (T * (T + 1) // 2)


def test_the_likely_bound_widens_its_margin_below_a_quarter_share():
    """No configuration sets a slack: ``ops.experts.likely_row_bound`` works
    it out of the share held. At a quarter and more it is ``SLACK`` x the
    even share; below, ``SLACK``'s margin grows by (1/4 / share)^1/2, the
    law a share's load spreads by: twice the even share at the cell's 1/64."""
    assert model().expert_likely_row_bound(8192) == experts_op.likely_row_bound(
        8192 * 2, 2, 16)
    assert experts_op.likely_row_bound(8192 * 8, 8, 512) == 2048
    assert experts_op.likely_row_bound(8192 * 8, 128, 512) == (
        experts_op.row_bound_for(int(experts_op.SLACK * 8192 * 8 // 4)))
    # 2 of 16: 1 + 0.25 x 2^1/2 = 1.354 x 2048 pairs, in whole tiles
    assert experts_op.likely_row_bound(8192 * 2, 2, 16) == 3072
