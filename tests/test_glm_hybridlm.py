"""``HybridLM`` of the ``glm4_moe_lite`` family (GLM-4.7-Flash: latent
attention with a low-rank query in every layer, sigmoid top-k routed experts
beside a shared one, a multi-token-prediction module whose loss counts)
at the configuration's rehearsal sizes: the tree; the two uses of the
embedding and the head; a weight of zero; ``from_config``; the facts. The
comparison with the benchmark's plain reference and the share test are
``test_glm_reference.py``'s, the mutations the comparison must catch
``test_glm_mutations.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import glm_hybrid_model as gm
from glm_hybrid_model import no_persistent_cache  # noqa: F401 - autouse
from glm_hybrid_model import T, V, model
from glm_hybrid_model import config as _config
from benchmark.reference import glm_moe_lite as ref
from raydp_tpu.models import HybridLM, LatentMTPHybridLM


@pytest.fixture(scope="module")
def batch():
    return gm.batch()


def test_the_parameter_tree_is_the_stage_of_the_published_model(batch):
    """The leaves at the rehearsal's widths, name by name, and the count at
    the PUBLISHED widths from the built tree (``jax.eval_shape``): ISSUE 53's
    706,518,848."""
    m = model(LatentMTPHybridLM)
    shapes = jax.eval_shape(
        lambda r: m.init(r, batch, None, method="loss"),
        jax.random.PRNGKey(0))["params"]
    d, heads = 64, 2
    mixer = {"wqa": (d, 24), "q_norm": (24,), "wqb": (24, heads * 24),
             "wkva": (d, 32 + 8), "kv_norm": (32,),
             "wkvb": (32, heads * (16 + 16)), "wo": (heads * 16, d),
             "norm1": (d,), "norm2": (d,)}
    experts = {"router": (d, 16), "expert_bias": (16,), "w13": (2, d, 64),
               "w2": (2, 32, d), "shared_in": (d, 64), "shared_out": (32, d)}
    assert sorted(shapes) == ["embed", "final_norm", "head", "layer_0",
                              "layer_1", "layer_2", "layer_3", "layer_4",
                              "mtp_0"]
    assert {k: v.shape for k, v in shapes["layer_0"].items()} == {
        **mixer, "w_in": (d, 2 * 96), "w_out": (96, d)}
    for name in ("layer_1", "layer_4"):
        assert {k: v.shape for k, v in shapes[name].items()} == {
            **mixer, **experts}
    assert {k: v.shape for k, v in shapes["mtp_0"].items()} == {
        **mixer, **experts, "eh_proj": (2 * d, d), "enorm": (d,),
        "hnorm": (d,), "final_norm": (d,)}
    assert shapes["head"].shape == (d, V) and shapes["embed"].shape == (V, d)
    from tpu_compile_helpers import cell_config
    published = cell_config("glm-4.7-flash")
    big = LatentMTPHybridLM.from_config(published, **published["model"]["kwargs"])
    tree = jax.eval_shape(
        lambda r: big.init(r, jnp.zeros((1, 8193), jnp.int32), None,
                           method="loss"), jax.random.PRNGKey(0))
    assert sum(leaf.size for leaf in jax.tree.leaves(tree)) == 706_518_848


def test_the_shared_embedding_and_head_take_the_sum_of_both_uses(batch):
    """``embed`` and ``head`` are read by the main model AND by the module:
    the program's gradient of each is the reference's gradient of the main
    loss plus 0.3 x its gradient of the module's, and neither part is 0."""
    m = model()
    p = gm.params(m, batch)
    _, _, routing, grads, _, _ = gm.program(m, p, batch)
    cfg = ref.config_of(gm.CONFIG)

    def part(name):
        return jax.grad(lambda q: ref.loss(q, batch, cfg, routing=routing)[1][
            name])(p)["params"]

    with jax.default_matmul_precision("highest"):
        main, module = part("main_loss"), part("mtp_loss")
    for leaf in ("embed", "head"):
        a, b = main[leaf], module[leaf]
        assert float(jnp.linalg.norm(a)) > 0 and float(jnp.linalg.norm(b)) > 0
        both = a + cfg["mtp_weight"] * b
        got = grads["params"][leaf]
        assert float(jnp.linalg.norm(got - both)) <= 1e-4 * float(
            jnp.linalg.norm(both)), leaf
        assert float(jnp.linalg.norm(got - a)) > 1e-2 * float(
            jnp.linalg.norm(both)), leaf
    # the module's own leaves take the module's loss alone
    assert all(float(jnp.abs(leaf).max()) == 0 for name, leaf in
               main["mtp_0"].items() if name != "expert_bias")


def test_a_weight_of_zero_builds_nothing_of_the_module(batch):
    """``mtp_weight`` 0 (``from_config``'s own, where no caller weighs the
    module the configuration has): no leaf, no term, no report;
    what is left is the model with the module, leaf for leaf and bit for bit,
    and Ling's tree (whose config weighs its module 0) is what it was."""
    with_module, without = model(), model(mtp_weight=0.0)
    assert not without.mtp_built
    assert not HybridLM.from_config(_config()).mtp_built
    assert "mtp_loss" not in without.train_report
    assert with_module.train_report[-1] == "mtp_loss"
    init = jax.jit(lambda m: m.init(jax.random.PRNGKey(0), batch, None,
                                    method="loss"), static_argnums=0)
    a, b = init(with_module)["params"], init(without)["params"]
    assert sorted(set(a) - set(b)) == ["mtp_0"]
    assert all(bool((x == y).all()) for x, y in zip(
        jax.tree.leaves({k: a[k] for k in b}), jax.tree.leaves(b)))
    loss, aux = without.apply({"params": b}, batch, None, True, method="loss")
    assert "mtp_loss" not in aux and "mtp_hidden" not in aux
    assert aux["routing"].shape[0] == 4
    import ling_hybrid_model as lm
    ling, weighed = lm.model(), lm.model(mtp_weight=0.0)
    x = lm.batch()
    pa, pb = init(ling), init(weighed)
    assert jax.tree.structure(pa) == jax.tree.structure(pb)
    assert all(bool((x_ == y_).all()) for x_, y_ in zip(
        jax.tree.leaves(pa), jax.tree.leaves(pb)))
    assert float(ling.apply(pa, x, method="loss")[0]) == float(
        weighed.apply(pb, x, method="loss")[0])


@pytest.mark.parametrize("change, match", [
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"attention_bias": True}, "attention_bias"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"num_nextn_predict_layers": 2}, "num_nextn_predict_layers"),
    ({"partial_rotary_factor": 0.5}, "partial_rotary_factor"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"num_key_value_heads": 1}, "num_key_value_heads"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"model_type": "glm4_moe"}, "glm4_moe_lite, not 'glm4_moe'"),
])
def test_from_config_refuses_what_the_family_does_not_build(change, match):
    with pytest.raises(ValueError, match=match):
        HybridLM.from_config(_config(**change))


def test_a_weight_that_no_module_can_carry_is_refused(batch):
    with pytest.raises(ValueError, match="mtp_weight -0.1"):
        jax.eval_shape(lambda: model(mtp_weight=-0.1).init(
            jax.random.PRNGKey(0), batch, None, method="loss"))
    with pytest.raises(ValueError, match="does not have"):
        model(config=_config(num_nextn_predict_layers=0))


def test_the_familys_fields_follow_the_config():
    m = model()
    assert (m.num_heads, m.head_dim, m.rope_head_dim, m.value_width,
            m.latent_rank, m.query_rank, m.latent_gate) == (
                2, 24, 8, 16, 32, 24, False)
    assert (m.experts_held, m.experts_total, m.first_expert,
            m.experts_per_token, m.shared_experts, m.expert_groups,
            m.expert_groups_kept, m.routed_scaling) == (
                2, 16, 2, 2, 1, 0, 0, 1.8)
    assert m.expert_weight_eps == 1e-20 and not m.tied_head
    assert (m.mtp_weight, m.rms_eps) == (0.3, 1e-5)
    assert m.attention_multiplier == 24 ** -0.5
    # a pipeline stage further on: no dense layer among its layers
    later = HybridLM.from_config(_config(share={
        "first_layer": 5, "experts_total": 16}))
    assert later.ffn_kinds == ("experts",) * 5
    # the group limit, where a config of the family has one, is the built one
    grouped = HybridLM.from_config(_config(n_group=4, topk_group=2))
    assert (grouped.expert_groups, grouped.expert_groups_kept) == (4, 2)
    assert HybridLM.from_config(_config(num_nextn_predict_layers=0)
                                ).mtp_built is False


def test_fit_facts_and_epoch_facts_reckon_the_module(batch):
    m = model(attn_impl="flash", dtype=jnp.bfloat16)
    facts = m.fit_facts(batch)
    want = {
        "layer_kinds": "mla,mla,mla,mla,mla", "layer_kinds.mla": 5,
        "attention.latent_rank": 32, "attention.query_rank": 24,
        "attention.key_width": 24, "attention.value_width": 16,
        "attention_backward": "latent=fused",
        "attention.backward_fused_layers": 6,
        "experts.held": 2, "experts.total": 16, "experts.per_token": 2,
        "experts.layers": 5, "experts.shared": 1,
        "mtp.weight": 0.3, "mtp.block": "mla,experts"}
    assert {k: facts[k] for k in want} == want
    assert "experts.groups" not in facts
    parts, d, f = m.flops_per_row_parts(T), 64, 32
    mixer = d * 24 + 24 * 48 + d * 40 + 32 * 64 + 32 * d
    assert parts["attention"] == 6 * 6 * 2 * (24 + 16) * (T * (T + 1) // 2)
    assert parts["head"] == 2 * 6 * d * V * T
    assert parts["layers"] == 6 * T * (
        6 * mixer + 3 * d * 96 + 5 * (d * 16 + 3 * d * f) + 2 * d * d)
    assert parts["experts"] == 5 * 6 * 3 * d * f * (T * 2 * 2 // 16)
    kept = m._remat_keeps(T)
    assert kept["attn_out"] == 6 * T * 2 * 16 * 2
    assert kept["mlp_out"] == 6 * T * d * 2
    without = model(attn_impl="flash", dtype=jnp.bfloat16, mtp_weight=0.0)
    assert without.fit_facts(batch)["attention.backward_fused_layers"] == 5
    assert "mtp.weight" not in without.fit_facts(batch)
    said = m.epoch_facts({"expert_load": np.full((5, 2), 30.0),
                          "pairs_dropped": 0.0, "layers_at_full_bound": 0.0,
                          "mtp_loss": 16.5}, 3)
    assert said["gauges"]["mtp.loss"] == pytest.approx(5.5)
    assert said["gauges"]["experts.likely_bound_share"] == 1.0
    assert "mtp.loss" not in without.epoch_facts(
        {"expert_load": np.full((4, 2), 30.0), "pairs_dropped": 0.0,
         "layers_at_full_bound": 0.0}, 3).get("gauges", {})
