"""Window layers and the ``smallthinker`` family at tiny sizes, float32, seeded
random weights (the flash kernels' window alone is
``tests/test_flash_attention.py``'s): the
router's two scoring rules and the experts' two activations against plain
``jnp`` (``HybridLM.from_config`` on the catalog row's keys against the plain
reference in loss, logits, every gradient and the selection:
``tests/test_windowed_routed_reference.py``); the four shares' expert
parts against the uncut reference; the refusals of ``from_config``;
``fit_facts``; and a JaxEstimator fit through the normal path."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import window_costs  # noqa: E402
from raydp_tpu.models import HybridLM, hybridlm_optimizer  # noqa: E402
from raydp_tpu.ops import experts  # noqa: E402
from windowed_routed_model import (  # noqa: E402, F401 - fixtures by name
    CFG, CONFIG, T, V, W, batch, gaps, model, objective, params, ref)

# -- (d) the router's two rules, the experts' two activations ----------------------

N, D, F, E, K = 48, 16, 8, 8, 3


@pytest.fixture(scope="module")
def layer():
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    return {"u": jax.random.normal(keys[0], (N, D)),
            "r": jax.random.normal(keys[5], (N, D)),
            "w_gate": jax.random.normal(keys[1], (D, E)),
            "bias": 0.3 * jax.random.normal(keys[2], (E,)),
            "w13": 0.3 * jax.random.normal(keys[3], (E, D, 2 * F)),
            "w2": 0.3 * jax.random.normal(keys[4], (E, F, D))}


def test_softmax_over_the_selected_is_a_plain_top_k_and_softmax(layer):
    with jax.default_matmul_precision("highest"):
        sel, w = experts.route(layer["u"], layer["w_gate"], None, K,
                               scoring="softmax")
        logits = layer["u"] @ layer["w_gate"]
    top, ids = jax.lax.top_k(logits, K)
    assert np.array_equal(np.asarray(sel), np.asarray(ids))
    np.testing.assert_allclose(w, jax.nn.softmax(top, axis=-1), rtol=1e-6)
    np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)


def test_the_sigmoid_rule_is_what_it_was(layer):
    with jax.default_matmul_precision("highest"):
        sel, w = experts.route(layer["u"], layer["w_gate"], layer["bias"], K,
                               scaling=2.0)
        scores = jax.nn.sigmoid(layer["u"] @ layer["w_gate"])
    _, ids = jax.lax.top_k(scores + layer["bias"], K)
    picked = jnp.take_along_axis(scores, ids, axis=-1)
    assert np.array_equal(np.asarray(sel), np.asarray(ids))
    np.testing.assert_allclose(
        w, 2.0 * picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)


def test_the_rules_refuse_what_they_are_not():
    u, w_gate = jnp.ones((4, D)), jnp.ones((D, E))
    with pytest.raises(ValueError, match="takes no bias"):
        experts.route(u, w_gate, jnp.zeros((E,)), K, scoring="softmax")
    with pytest.raises(ValueError, match="not one of"):
        experts.route(u, w_gate, None, K, scoring="tanh")
    with pytest.raises(ValueError, match="activation"):
        experts.routed_experts(u, w_gate, None, jnp.ones((E, D, 2 * F)),
                               jnp.ones((E, F, D)), first=0, top_k=K,
                               scoring="softmax", activation="gelu")


def _masked(p, first, count, activation, routed_from):
    """Every held expert on every token under a 0/1 mask, in plain jnp."""
    logits = p[routed_from] @ p["w_gate"]
    top, sel = jax.lax.top_k(logits, K)
    w = jax.nn.softmax(top, axis=-1)
    act = {"relu": jax.nn.relu, "silu": jax.nn.silu}[activation]
    out = jnp.zeros_like(p["u"])
    for e in range(first, first + count):
        h = p["u"] @ p["w13"][e]
        y = (act(h[:, :F]) * h[:, F:]) @ p["w2"][e]
        out = out + jnp.where(sel == e, w, 0).sum(-1)[:, None] * y
    return out


@pytest.mark.parametrize("activation", ["relu", "silu"])
@pytest.mark.parametrize("routed_from", ["u", "r"])
@pytest.mark.parametrize("first, count", [(0, 8), (2, 2)])
def test_the_layer_under_the_second_rule_is_the_masked_sum(
        layer, first, count, activation, routed_from):
    names = ("u", "r", "w_gate", "w13", "w2")

    def ours(*args):
        p = dict(zip(names, args))
        with jax.default_matmul_precision("highest"):
            out, report = experts.routed_experts(
                p["u"], p["w_gate"], None, p["w13"][first:first + count],
                p["w2"][first:first + count], first=first, top_k=K,
                scoring="softmax", activation=activation,
                router_input=p["r"] if routed_from == "r" else None)
        return (out * jnp.cos(jnp.arange(D))).sum(), (out, report)

    def theirs(*args):
        with jax.default_matmul_precision("highest"):
            out = _masked(dict(zip(names, args)), first, count, activation,
                          routed_from)
        return (out * jnp.cos(jnp.arange(D))).sum(), out

    args = tuple(layer[name] for name in names)
    (_, (got, report)), g_got = jax.value_and_grad(
        ours, (0, 1, 2, 3, 4), has_aux=True)(*args)
    (_, want), g_want = jax.value_and_grad(
        theirs, (0, 1, 2, 3, 4), has_aux=True)(*args)
    assert float(report["dropped"]) == 0
    assert float(jnp.abs(got - want).max()) <= 1e-5
    for name, a, b in zip(names, g_got, g_want):
        assert float(jnp.abs(a - b).max()) <= 1e-4 * max(
            1.0, float(jnp.abs(b).max())), name
    if routed_from == "u":  # nothing reads r
        assert float(jnp.abs(g_got[1]).max()) == 0


def test_reglu_is_relu_of_the_gate_times_up(layer):
    """One expert holding every pair: the layer is that expert's ReGLU."""
    u = layer["u"]
    out, _ = experts.routed_experts(
        u, jnp.zeros((D, 1)), None, layer["w13"][:1], layer["w2"][:1],
        first=0, top_k=1, scoring="softmax", activation="relu")
    h = u @ layer["w13"][0]
    want = (jnp.maximum(h[:, :F], 0) * h[:, F:]) @ layer["w2"][0]
    np.testing.assert_allclose(out, want, rtol=1e-5, atol=1e-6)


# -- (c) THE SHARE TEST -------------------------------------------------------------


def test_the_four_shares_expert_parts_add_up_to_the_uncut_layer(batch):
    """One layer at a tiny size: the expert parts the four shares give
    (experts 0-3, 4-7, 8-11, 12-15 of 16; the same router, attention and
    norms on each) add up to what the UNCUT reference gives for the whole
    layer's experts."""
    whole = dict(CONFIG, num_hidden_layers=4, moe_num_primary_experts=16,
                 share={"first_layer": 0})
    uncut = model(whole).init(jax.random.PRNGKey(2), batch, None,
                              method="loss")["params"]["layer_1"]
    keys = jax.random.split(jax.random.PRNGKey(3), 2)
    h = jax.random.normal(keys[0], (2, T, 48))  # the block's input
    y = jax.random.normal(keys[1], (2, T, 48))  # the experts' input
    with jax.default_matmul_precision("highest"):
        want, _, _ = ref._experts(
            uncut, h, y, dict(ref.config_of(whole), first_expert=0), None,
            False)
    total = jnp.zeros_like(want)
    own = model().init(jax.random.PRNGKey(4), batch, None, method="loss")
    for first in (0, 4, 8, 12):
        share = dict(CONFIG, share={
            "first_layer": 0, "experts_total": 16, "first_expert": first})
        module = model(share)
        assert (module.first_expert, module.experts_held) == (first, 4)
        w = {**uncut, "w13": uncut["w13"][first:first + 4],
             "w2": uncut["w2"][first:first + 4]}
        with jax.default_matmul_precision("highest"):
            # the module's own expert layer on the uncut layer's weights
            # (bound to a tree of its own, which the call does not read)
            out, report = module.apply(
                own, w, y, h, method=lambda m, w, y, h: m._experts(w, y, h))
        assert float(report["dropped"]) == 0
        total = total + out
        # and the reference, given the same share, gives the same part
        with jax.default_matmul_precision("highest"):
            part, _, _ = ref._experts(w, h, y, ref.config_of(share), None,
                                      False)
        np.testing.assert_allclose(out, part, atol=2e-6)
    np.testing.assert_allclose(total, want, atol=5e-6)


# -- (e) from_config ------------------------------------------------------------------


def test_the_parameter_tree_is_the_stage_of_the_published_model(params):
    module = model()
    assert module.layer_types == ("attention",) * 4
    assert module.ffn_kinds == ("experts",) * 4
    assert module.layer_windows == (0, W, W, W)
    assert module.layer_ropes == (False, True, True, True)
    assert (module.head_dim, module.attention_width) == (16, 64)
    assert (module.first_expert, module.experts_held, module.experts_total,
            module.experts_per_token) == (4, 4, 16, 3)
    shapes = jax.tree.map(lambda a: a.shape, params["params"])
    assert shapes["embed"] == (V, 48) and shapes["head"] == (48, V)
    assert shapes["layer_0"] == {
        "norm1": (48,), "norm2": (48,), "router": (48, 16),
        "w13": (4, 48, 48), "w2": (4, 24, 48), "wq": (48, 64),
        "wk": (48, 32), "wv": (48, 32), "wo": (64, 48)}
    assert "expert_bias" not in shapes["layer_0"]
    # the family is model_type's word alone, as in the source's config.json
    untyped = {k: v for k, v in CONFIG.items() if k != "model_type"}
    with pytest.raises(ValueError, match="tie_word_embeddings=True only"):
        HybridLM.from_config(untyped)  # read as the default family's


def test_rope_and_window_are_independent_keys(batch):
    config = dict(CONFIG, rope_layout=[1, 0, 0, 1] * 3)
    module = model(config)
    assert module.layer_ropes == (True, False, False, True)
    assert module.layer_windows == (0, W, W, W)
    p = module.init(jax.random.PRNGKey(1), batch, None, method="loss")
    got = objective(module.clone(attn_impl="flash"), p, batch)
    want = ref.loss_and_grads(p, batch, ref.config_of(config),
                              with_states=True)
    assert max(gaps(got, want)[:3]) <= 2e-5


@pytest.mark.parametrize("change, match", [
    ({"share": {"first_layer": 2}}, "whole number of periods"),
    ({"num_hidden_layers": 3}, "whole number of periods"),
    ({"num_hidden_layers": 6}, "whole number of periods"),
    ({"share": {"first_layer": 8}, "num_hidden_layers": 8},
     "whole number of periods"),
    ({"moe_primary_router_apply_softmax": False}, "sigmoid rule"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"model_type": "qwen3_moe"}, "not 'qwen3_moe'"),
    ({"share": {"first_layer": 4, "experts_total": 16, "first_expert": 14}},
     "not an expert layer's share"),
])
def test_from_config_refuses_what_the_model_does_not_build(
        batch, change, match):
    with pytest.raises(ValueError, match=match):
        module = HybridLM.from_config({**CONFIG, **change})
        module.init(jax.random.PRNGKey(0), batch, None, method="loss")


def test_a_whole_number_of_periods_is_built():
    two = HybridLM.from_config(dict(CONFIG, num_hidden_layers=8))
    assert two.layer_windows == (0, W, W, W) * 2
    with pytest.raises(ValueError, match="each of 4 layers"):
        model().clone(attention_windows=(0, W)).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 9), jnp.int32), None,
            method="loss")
    with pytest.raises(ValueError, match="no head_dim"):
        HybridLM(vocab_size=8, hidden_size=50, num_heads=4)
    assert HybridLM(vocab_size=8).head_dim == 64  # hidden_size // num_heads


# -- placement: where a group puts the experts of a router without a bias --------------


@pytest.mark.parametrize("loads, chips", [
    ([5, 1, 1, 1, 9, 2, 2, 3, 7, 1, 4, 4, 6, 2, 8, 8], 4),
    (list(np.random.default_rng(0).zipf(1.5, 64).clip(max=300)), 4),
    ([3.0] * 8, 2),
    ([100, 100, 100, 100, 100, 100] + [0] * 58, 4),  # six lumps: 2, 2, 1, 1
])
def test_place_deals_the_experts_to_chips_of_near_even_load(loads, chips):
    order = experts.place(loads, chips)
    assert sorted(order) == list(range(len(loads)))
    assert experts.place(list(loads), chips) == order  # the same loads, the same
    per = len(loads) // chips
    sums = [sum(loads[e] for e in order[c * per:(c + 1) * per])
            for c in range(chips)]
    # no chip is further from another than the largest expert's load, and
    # no single swap between the fullest and the emptiest would narrow it
    assert max(sums) - min(sums) <= max(loads)
    hi, lo = int(np.argmax(sums)), int(np.argmin(sums))
    gap = sums[hi] - sums[lo]
    for a in order[hi * per:(hi + 1) * per]:
        for b in order[lo * per:(lo + 1) * per]:
            assert abs(gap - 2 * (loads[a] - loads[b])) >= gap - 1e-9
    with pytest.raises(ValueError, match="over 3 chips"):
        experts.place(loads, 3)


def _held_share(module, p, x):
    """This chip's share of every expert layer's pairs over the even one."""
    routing = np.asarray(module.apply(
        p, x, None, True, method="loss")[1]["routing"])
    first, held = module.first_expert, module.experts_held
    here = (routing >= first) & (routing < first + held)
    return here.reshape(len(routing), -1).mean(axis=1) * (
        module.experts_total / held)


def test_placed_by_load_permutes_the_seeded_routers_columns_and_no_more(batch):
    module, rng = model(), jax.random.PRNGKey(7)
    seeded = module.init(rng, batch, None, method="loss")
    placed, before, after = module.placed_by_load(rng, [batch])
    assert placed.clone(expert_placement=()) == module
    got = placed.init(rng, batch, None, method="loss")
    for i, order in enumerate(placed.expert_placement):
        assert sorted(order) == list(range(module.experts_total))
        for name, leaf in seeded["params"][f"layer_{i}"].items():
            want = leaf[:, np.asarray(order)] if name == "router" else leaf
            np.testing.assert_array_equal(got["params"][f"layer_{i}"][name],
                                          want, err_msg=name)
    for name in ("embed", "head", "final_norm"):
        np.testing.assert_array_equal(got["params"][name],
                                      seeded["params"][name])
    # what it said of the load is what the two models' routers do, and the
    # placed chip is no further from the even share than the seeded one
    np.testing.assert_allclose(_held_share(placed, got, batch), after,
                               rtol=1e-6)
    np.testing.assert_allclose(_held_share(module, seeded, batch)[0],
                               before[0], rtol=1e-6)
    assert max(abs(v - 1) for v in after) <= max(abs(v - 1) for v in before)
    # the reference, which knows nothing of placement, agrees on the placed
    # parameters as on any others
    loss, aux = placed.apply(got, batch, None, True, method="loss")
    want = ref.loss_and_grads(got, batch, CFG, with_states=True)
    assert abs(float(loss) - float(want[0])) <= 2e-5


def test_placement_is_refused_where_it_has_no_meaning(batch):
    module = model()
    placed, _, _ = module.placed_by_load(jax.random.PRNGKey(0), [batch])
    with pytest.raises(ValueError, match="unplaced"):
        placed.placed_by_load(jax.random.PRNGKey(0), [batch])
    with pytest.raises(ValueError, match="equal"):  # experts 2-5 of 16
        model(dict(CONFIG, share={**CONFIG["share"], "first_expert": 2})
              ).placed_by_load(jax.random.PRNGKey(0), [batch])
    with pytest.raises(ValueError, match="permutation"):
        module.clone(expert_placement=((0, 1),) * 4).init(
            jax.random.PRNGKey(0), batch, None, method="loss")


def test_the_embeddings_spread_is_its_own_and_the_default_is_the_matrices(batch):
    module, rng = model(), jax.random.PRNGKey(3)
    seeded = module.init(rng, batch, None, method="loss")["params"]
    wide = module.clone(embed_std=1.0).init(
        rng, batch, None, method="loss")["params"]
    np.testing.assert_allclose(wide["embed"], 50 * seeded["embed"], rtol=1e-6)
    np.testing.assert_array_equal(wide["head"], seeded["head"])
    np.testing.assert_array_equal(wide["layer_2"]["router"],
                                  seeded["layer_2"]["router"])
    assert abs(float(jnp.std(seeded["embed"])) - 0.02) < 2e-3


# -- facts, costs, the fit -----------------------------------------------------------


@pytest.mark.parametrize("sizes", ["published", "tiny"])
def test_the_models_flops_are_the_benchmarks_count(sizes):
    if sizes == "published":
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "smallthinker-21b-a3b.json")) as f:
            config, t = json.load(f), 16384
    else:
        config, t = CONFIG, T
    module = HybridLM.from_config(config)
    parts = module.flops_per_row_parts(t)
    costs = window_costs.step_flops(config, 1, t)
    assert {k: parts[k] for k in ("layers", "attention", "head", "experts")
            } == {k: costs[k] for k in ("layers", "attention", "head",
                                        "experts")}
    facts = module.fit_facts(np.zeros((2, t + 1), np.int32))
    assert facts["flops_per_row"] == costs["total"]
    window = config["sliding_window_size"]
    assert facts["attention.pairs_per_row"] == (
        t * (t + 1) // 2 + 3 * window_costs.window_pairs(t, window))
    assert (facts["layer_kinds.window"], facts["layer_kinds.global"],
            facts["attention.window"]) == (3, 1, window)
    if sizes == "published":
        assert facts["attention.pairs_per_row"] == 3 * 58_722_304 + 134_225_920
        assert (facts["experts.held"], facts["experts.total"],
                facts["experts.per_token"]) == (16, 64, 6)
        assert module.expert_likely_row_bound(32768) == 61_440
        assert module.expert_row_bound(32768) == 196_608
        assert experts.token_rows_gathered(61_440, 32768, 6) == 94_208
        kernels = window_costs.kernels(config, 2, t)
        assert kernels["flash_window_fwd"]["cost"]["flops"] == (
            56 * 4 * 128 * 58_722_304)
        assert kernels["flash_window_bwd"]["cost"]["bytes"] == (
            kernels["flash_bwd"]["cost"]["bytes"])


@pytest.mark.parametrize("impl, tokens, want", [
    # the cell: one global and three window layers, each backward pass the
    # ONE fused call; a head's float32 dq [16384, 128] stays in VMEM
    ("flash", 16384, ("global=fused,window=fused", 4, 16384 * 128 * 4)),
    # 128k tokens: a dq of 64 MB is past what a call may ask for
    ("flash", 131072, ("global=two_call,window=two_call", 0, 0)),
    # no flash kernel runs: autodiff's backward
    ("full", 16384, ("global=xla,window=xla", 0, 0))])
def test_fit_facts_say_which_form_the_attention_backward_takes(
        impl, tokens, want):
    """PR 43: by layer kind, from ``attn_impl`` and the shapes, as the
    kernel decides it (``ops.flash_attention.backward_form``); at the
    published widths."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        module = HybridLM.from_config(json.load(f), attn_impl=impl)
    facts = module.fit_facts(np.zeros((2, tokens + 1), np.int32))
    assert (facts["attention_backward"],
            facts["attention.backward_fused_layers"],
            facts["attention.dq_resident_bytes"]) == want
    # heads of 64 are padded to the 128 lanes in VMEM: Granite's one layer
    narrow = HybridLM(vocab_size=50176, attn_impl=impl).fit_facts(
        np.zeros((1, 8193), np.int32))
    form = want[0].split(",")[0] if tokens == 16384 else "global=fused"
    assert narrow["attention_backward"] == form
    if impl == "flash":
        assert (narrow["attention.backward_fused_layers"],
                narrow["attention.dq_resident_bytes"]) == (1, 8192 * 128 * 4)


@pytest.mark.parametrize("impl, tokens, want", [
    # the cell: the global layer's calls step over the 136 tiles under the
    # diagonal of 16 x 16, the window layers' over the window's bounded grid
    ("flash", 16384, ("global=live,window=window", 100.0)),
    # a window of the whole row hides nothing: the causal call, live
    ("flash", 4096, ("global=live,window=live", 100.0)),
    # a ring step's offsets are values of the program: 136 of 256 steps live
    ("ring_flash", 16384, (
        "global=rectangular:runtime offsets,"
        "window=rectangular:runtime offsets", 53.125)),
    ("full", 16384, ("global=xla,window=xla", None))])
def test_fit_facts_say_which_grid_the_attention_calls_step_over(
        impl, tokens, want):
    """ISSUE 54: by layer kind, from ``attn_impl``, the window and the
    shapes, as the kernel decides it (``ops.flash_attention.causal_grid``);
    at the published widths."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker-21b-a3b.json")) as f:
        module = HybridLM.from_config(json.load(f), attn_impl=impl)
    facts = module.fit_facts(np.zeros((2, tokens + 1), np.int32))
    assert (facts["attention_grid"],
            facts.get("attention.causal_grid_live_share")) == want


def test_a_model_without_window_layers_says_nothing_of_them():
    facts = HybridLM(vocab_size=64, hidden_size=32, num_heads=4,
                     num_kv_heads=2, intermediate_size=48,
                     layer_types=("attention",)).fit_facts(
                         np.zeros((1, 17), np.int32))
    assert not [k for k in facts if "window" in k or "pairs_per_row" in k]


def test_the_balancing_rule_with_no_bias_to_move_says_so_and_does_not_fail(
        params, batch, caplog):
    import logging

    tx = hybridlm_optimizer(warmup_steps=4, expert_bias_rate=0.05)
    with caplog.at_level(logging.WARNING):
        state = tx.init(params)
    assert "no expert_bias" in caplog.text
    (_, _), grads = objective(model(), params, batch)
    updates, _ = tx.update(grads, state, params)
    plain = hybridlm_optimizer(warmup_steps=4)
    want, _ = plain.update(grads, plain.init(params), params)
    for a, b in zip(jax.tree.leaves(updates), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)


def test_estimator_fit_lowers_held_out_loss_and_reports_the_training_load():
    """ETL -> store -> exchange -> JaxEstimator.fit(loss="model"), no
    estimator argument of its own: the resident scan runner trains the
    family as it trains the routed one, the loss falls, the training steps'
    report and the window layers' gauges are there."""
    from jax.sharding import Mesh

    import raydp_tpu
    from raydp_tpu import obs
    from raydp_tpu.cluster import api as cluster
    from raydp_tpu.estimator import JaxEstimator

    motif = np.random.default_rng(3).integers(0, V, 4)
    ids = np.tile(motif, (12, (T + 1) // 4 + 1))[:, :T + 1].astype(np.int32)
    table = pa.table({"tokens": pa.FixedSizeListArray.from_arrays(
        pa.array(ids.ravel()), ids.shape[1])})
    session = raydp_tpu.init_etl("windowlm", num_executors=1, executor_cores=1,
                                 executor_memory="500M")
    try:
        df = session.from_arrow(table, num_partitions=2)
        est = JaxEstimator(
            model=model(attn_impl="flash"), loss="model",
            feature_columns=["tokens"], feature_dtype=np.int32,
            label_column=None, batch_size=2,
            mesh=Mesh(np.array(jax.devices()[:1]), ("data",)),
            optimizer=hybridlm_optimizer(3e-3), num_epochs=3, seed=0)
        with jax.default_matmul_precision("highest"):
            history = est.fit_on_etl(df.limit(8), df.limit(4))
    finally:
        raydp_tpu.stop_etl()
        cluster.shutdown()
    assert history[-1]["eval_loss"] < history[0]["eval_loss"] - 0.1
    assert est.fit_stats_["runner"] == "resident_scan"
    assert all(rec["train_report"]["expert_load"].shape == (4, 4)
               for rec in history)
    assert history[-1]["eval_pairs_dropped"] == [0.0]
    snap = obs.metrics.snapshot()
    for gauge, fact in (("layer_kinds.window", 3), ("layer_kinds.global", 1),
                        ("attention.window", W), ("experts.held", 4),
                        ("experts.total", 16), ("experts.per_token", 3),
                        ("attention.pairs_per_row",
                         T * (T + 1) // 2 + 3 * window_costs.window_pairs(T, W))):
        assert snap[f"model.{gauge}"]["value"] == fact, gauge
