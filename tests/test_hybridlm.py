"""The hybrid state-space / attention LM (models/hybridlm.py) at tiny sizes,
float32, seeded random weights: against the plain reference
(benchmark/reference/granite_hybrid.py: the per-token recurrence, full softmax
attention a head group at a time) in loss, logits and every gradient; the
mutations the comparison must catch; the parameter tree built from
``layer_types``; ``fit_facts``; and a JaxEstimator fit on an ETL frame whose
epoch program is held to the reference's gradients through its AdamW."""

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

from benchmark.reference import granite_hybrid as ref  # noqa: E402
from raydp_tpu.models import HybridLM, hybridlm_optimizer  # noqa: E402
from raydp_tpu.models import hybridlm as hm  # noqa: E402
from raydp_tpu.models.looplm import apply_rope, rms_norm, rope_tables  # noqa: E402

V, T = 256, 32
# the published keys at tiny widths; both layer kinds, attention in the middle
CONFIG = {
    "vocab_size": V, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "shared_intermediate_size": 96,
    "layer_types": ["mamba", "attention", "mamba", "mamba"],
    "num_hidden_layers": 3, "mamba_n_heads": 8, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 8,
    "mamba_expand": 2, "mamba_n_groups": 1, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8, "rms_norm_eps": 1e-5}
CFG = ref.config_of(CONFIG)


def model(cls=HybridLM, **kw):
    return cls.from_config(CONFIG, **{"dtype": jnp.float32, "loss_chunk": 16,
                                      **kw})


@pytest.fixture(scope="module")
def batch():
    return jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0, V)


@pytest.fixture(scope="module")
def params(batch):
    """Seeded parameters, with the convolution's bias (zeros as initialised)
    and the norm gains moved off their initial values so that dropping or
    misplacing one shows."""
    p = model().init(jax.random.PRNGKey(0), batch, None, method="loss")
    flat = jax.tree_util.tree_leaves_with_path(p)
    keys = jax.random.split(jax.random.PRNGKey(2), len(flat))
    moved = [leaf + 0.1 * jax.random.normal(k, leaf.shape)
             if path[-1].key in ("conv_b", "gate_norm", "norm1", "norm2")
             else leaf for (path, leaf), k in zip(flat, keys)]
    return jax.tree.unflatten(jax.tree.structure(p), moved)


@pytest.fixture(scope="module")
def want(params, batch):
    """The reference's loss, gradients and logits on ``params``, once."""
    value, _, grads = jax.jit(
        lambda p, x: ref.loss_and_grads(p, x, CFG))(params, batch)
    return float(value), jax.tree.leaves(grads), jax.jit(
        lambda p, x: ref.forward(p, x, CFG))(params, batch[:, :-1])


def gaps(m, p, x, want):
    """The program's loss, logits and gradients against the reference's:
    (loss gap, logits gap relative to max |reference|, worst parameter's
    gradient gap in L2 relative to the reference's)."""
    want_loss, want_grads, want_logits = want

    @jax.jit
    def run(p, x):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda q: m.apply(q, x, method="loss"), has_aux=True)(p), (
                    m.apply(p, x[:, :-1]))

    ((loss, _), grads), logits = run(p, x)
    worst = max(
        float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-20))
        for a, b in zip(jax.tree.leaves(grads), want_grads))
    return (abs(float(loss) - want_loss),
            float(jnp.abs(logits - want_logits).max()
                  / jnp.abs(want_logits).max()), worst)


def test_system_against_the_reference(params, batch, want):
    loss_gap, logits_gap, grads_gap = gaps(model(), params, batch, want)
    assert loss_gap <= 1e-5 and logits_gap <= 1e-5 and grads_gap <= 1e-5


@pytest.mark.parametrize("form", [
    {"remat": False}, {"loss_chunk": 0}, {"mamba_chunk": 32},
    {"mamba_chunk": 4}])
def test_forms_agree(params, batch, want, form):
    """Recomputation, the loss's chunks and the scan's chunk size change no
    arithmetic."""
    loss_gap, logits_gap, grads_gap = gaps(model(**form), params, batch, want)
    assert loss_gap <= 1e-5 and logits_gap <= 1e-5 and grads_gap <= 1e-5


class NormBeforeGate(HybridLM):
    """Mamba-1's order: RMSNorm(y) silu(z) in place of RMSNorm(y silu(z))."""

    def _gated_norm(self, w, y, z):
        return rms_norm(y, w["gate_norm"], self.rms_eps) * jax.nn.silu(z)


def _zeroed(params, name):
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: jnp.zeros_like(leaf) if path[-1].key == name
        else leaf, params)


def _kv_heads_swapped(params):
    """K/V head 1 serves query heads 0-1 and head 0 heads 2-3."""
    def swap(path, leaf):
        if path[-1].key not in ("wk", "wv"):
            return leaf
        half = leaf.shape[1] // 2
        return jnp.concatenate([leaf[:, half:], leaf[:, :half]], axis=1)

    return jax.tree_util.tree_map_with_path(swap, params)


def _with_rope(monkeypatch):
    real = hm._attend

    def attend(q, k, v, **kw):
        cos, sin = rope_tables(q.shape[2], q.shape[3], 10000.0)
        return real(apply_rope(q, cos, sin), apply_rope(k, cos, sin), v, **kw)

    monkeypatch.setattr(hm, "_attend", attend)


MUTATIONS = {
    "D_dropped": lambda p: (model(), _zeroed(p, "D")),
    "conv_bias_dropped": lambda p: (model(), _zeroed(p, "conv_b")),
    "embedding_multiplier_left_out": lambda p: (
        model(embedding_multiplier=1.0), p),
    "residual_multiplier_left_out": lambda p: (
        model(residual_multiplier=1.0), p),
    "logits_scaling_left_out": lambda p: (model(logits_scaling=1.0), p),
    "attention_scale_is_rsqrt_head_dim": lambda p: (
        model(attention_multiplier=16 ** -0.5), p),
    "kv_heads_misgrouped": lambda p: (model(), _kv_heads_swapped(p)),
    "rope_applied": lambda p: (model(), p),
    "norm_before_the_gate": lambda p: (model(NormBeforeGate), p),
}


@pytest.mark.parametrize("mutation", sorted(MUTATIONS))
def test_a_mutation_fails_the_comparison(params, batch, want, mutation,
                                         monkeypatch):
    """Each departure from the published layer moves the loss, the logits or
    a gradient by 1e-3 and more: a hundred times the right program's gap."""
    if mutation == "rope_applied":
        _with_rope(monkeypatch)
    m, mutated = MUTATIONS[mutation](params)
    assert max(gaps(m, mutated, batch, want)) >= 1e-3


def test_the_parameter_tree_follows_layer_types():
    """The published pattern's first period: attention at index 5 of 10,
    nine Mamba-2 layers around it, one tied embedding; 849,230,784
    parameters at the published widths with half the vocabulary (ISSUE 31)."""
    big = HybridLM(vocab_size=50176)  # the defaults are the published widths
    assert big.layer_types.index("attention") == 5 and len(big.layer_types) == 10
    shapes = jax.eval_shape(
        lambda r, s: big.init(r, s, None, method="loss"), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 65), jnp.int32))["params"]
    assert sorted(shapes) == sorted(
        ["embed", "final_norm"] + [f"layer_{i}" for i in range(10)])
    for i in range(10):
        names = set(shapes[f"layer_{i}"])
        assert ("wq" in names) == (i == 5) and ("in_proj" in names) == (i != 5)
        assert {"norm1", "norm2", "w_in", "w_out"} <= names
    assert shapes["layer_0"]["in_proj"].shape == (2048, 2 * 4096 + 2 * 128 + 64)
    assert shapes["layer_0"]["conv_w"].shape == (4, 4096 + 2 * 128)
    assert shapes["layer_5"]["wk"].shape == (2048, 8 * 64)
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert count(shapes["layer_0"]) == 76_182_976
    assert count(shapes["layer_5"]) == 60_821_504
    assert count(shapes) == 849_230_784
    # every leaf float32, whatever the compute dtype
    assert {a.dtype for a in jax.tree.leaves(shapes)} == {jnp.dtype("float32")}


def test_from_config_refuses_what_the_model_does_not_build():
    for key, value in (("num_local_experts", 8), ("mamba_n_groups", 2),
                       ("position_embedding_type", "rope")):
        with pytest.raises(ValueError, match=key):
            HybridLM.from_config({**CONFIG, key: value})
    with pytest.raises(ValueError, match="layer kind"):
        model(layer_types=("mamba", "sliding")).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 9), jnp.int32), None,
            method="loss")


def test_published_initialisation_of_the_mamba_vectors(params):
    """dt = softplus(dt_bias) in [0.001, 0.1], A = exp(A_log) in [1, 16],
    D ones: at this a 256-token chunk's summed log-decay reaches -400."""
    w = model().init(jax.random.PRNGKey(4), jnp.zeros((1, 9), jnp.int32), None,
                     method="loss")["params"]["layer_0"]
    dt = jax.nn.softplus(w["dt_bias"])
    assert 1e-3 * 0.999 <= float(dt.min()) and float(dt.max()) <= 1e-1 * 1.001
    a = jnp.exp(w["A_log"])
    assert 1.0 <= float(a.min()) and float(a.max()) <= 16.0
    assert bool((w["D"] == 1.0).all()) and bool((w["conv_b"] == 0.0).all())


def test_fit_facts_say_what_a_row_holds():
    """Tokens, layer kinds and model FLOPs of a row come from the model:
    hand-worked at the published widths (ISSUE 31)."""
    big = HybridLM(vocab_size=50176, attn_impl="flash")
    facts = big.fit_facts(np.zeros((1, 8193), np.int32))
    t = 8192
    assert facts["tokens_per_row"] == t and facts["ssd_chunk"] == 256
    # what the loss does (ISSUE 32): its gradient in the forward sweep
    assert (facts["loss_grad"], facts["loss_products_per_chunk"]) == ("forward", 3)
    assert facts["layer_kinds.mamba"] == 9 and facts["layer_kinds.attention"] == 1
    assert facts["layer_kinds"].split(",").index("attention") == 5
    mamba = 2048 * 8512 + 4096 * 2048 + 3 * 2048 * 8192
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 3 * 2048 * 8192
    pairs = (t // 256) * (256 * 257 // 2)
    scan = 9 * (2 * 128 * pairs + 2 * 4096 * pairs + 4 * 4096 * 128 * t)
    assert facts["ssd_flops_per_row"] == 3 * scan
    assert facts["flops_per_row"] == (
        6 * (9 * mamba + attention + 9 * 4 * 4352) * t + 3 * scan
        + 12 * 2048 * (t * (t + 1) // 2) + 6 * 2048 * 50176 * t)
    # ISSUE 31's reckoning: 1.77e9 forward FLOPs a token, 4.35e13 a step
    assert facts["flops_per_row"] == pytest.approx(4.35e13, rel=0.01)
    assert facts["ssd_flops_per_row"] / facts["flops_per_row"] == pytest.approx(
        0.016, abs=0.003)
    wide = t * 2048 * 2
    assert facts["remat_keeps"] == "attn_out,attn_lse,mlp_out"
    assert facts["remat_kept_bytes_per_row"] == wide + 4 * 32 * t + 10 * wide
    plain = big.clone(attn_impl="full").fit_facts(np.zeros((1, 8193), np.int32))
    assert plain["remat_keeps"] == "mlp_out"
    off = big.clone(remat=False).fit_facts(np.zeros((1, 8193), np.int32))
    assert (off["remat"], off["remat_keeps"], off["remat_kept_bytes_per_row"]) == (
        False, "", 0)


@pytest.mark.parametrize("sizes", ["published", "tiny"])
def test_the_models_flops_are_the_benchmarks_count(sizes):
    """``fit_facts``'s ``flops_per_row`` (what the live ``estimator.mfu``
    counts) is ``benchmark/harness/ssm_costs.step_flops``'s total, part by
    part, at the benchmark's configuration and at this file's."""
    import json

    from benchmark.harness import ssm_costs

    if sizes == "published":
        with open(os.path.join(ROOT, "benchmark", "configs",
                               "granite-4.0-h-micro.json")) as f:
            config, t = json.load(f), 8192
    else:
        config, t = CONFIG, T
    module = HybridLM.from_config(config)
    parts = ssm_costs.step_flops(config, 1, t)
    assert module.flops_per_row_parts(t) == {
        k: v for k, v in parts.items() if k != "total"}
    facts = module.fit_facts(np.zeros((1, t + 1), np.int32))
    assert facts["flops_per_row"] == parts["total"]
    assert facts["ssd_flops_per_row"] == parts["scan"]


@pytest.mark.parametrize("remat", [True, False])
def test_the_backward_pass_runs_no_flash_forward(params, batch, want, remat):
    """Blocks recomputed, the attention layer's ``attn_out`` and
    ``attn_lse`` kept by name: the gradient holds as many flash forward
    calls as one that recomputes nothing (one: the one attention layer),
    and K/V heads repeated to the query heads."""
    m = model(attn_impl="flash", remat=remat)
    jaxpr = str(jax.make_jaxpr(jax.grad(
        lambda p: m.apply(p, batch, method="loss")[0]))(params))
    assert jaxpr.count("name=flash_attention_fwd") == 1
    assert max(gaps(m, params, batch, want)) <= 1e-5


# -- the estimator -------------------------------------------------------------

HYPER = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "weight_decay": 0.1}


def _session_fit(name, ids, est_kw, held_rows=0):
    """A JaxEstimator fit on a frame that came through the ETL, on one
    device (the resident scan runner), float32 / highest."""
    from jax.sharding import Mesh

    import raydp_tpu
    from raydp_tpu.cluster import api as cluster
    from raydp_tpu.estimator import JaxEstimator

    table = pa.table({"tokens": pa.FixedSizeListArray.from_arrays(
        pa.array(ids.ravel()), ids.shape[1])})
    session = raydp_tpu.init_etl(name, num_executors=1, executor_cores=1,
                                 executor_memory="500M")
    try:
        df = session.from_arrow(table, num_partitions=2)
        est = JaxEstimator(
            model=model(), loss="model", feature_columns=["tokens"],
            feature_dtype=np.int32, label_column=None, batch_size=2,
            mesh=Mesh(np.array(jax.devices()[:1]), ("data",)), **est_kw)
        with jax.default_matmul_precision("highest"):
            history = est.fit_on_etl(
                df.limit(len(ids) - held_rows),
                df.limit(held_rows) if held_rows else None)
    finally:
        raydp_tpu.stop_etl()
        cluster.shutdown()
    return est, history


def test_estimator_fit_lowers_held_out_loss_and_sets_the_gauges():
    """ETL -> store -> exchange -> JaxEstimator.fit(loss="model"), no label
    column, no estimator argument of its own: the loss falls, the resident
    scan runner ran the dense step, tokens are counted, and what the model
    says of itself is on the compile spans and in the gauges."""
    from raydp_tpu import obs

    motif = np.random.default_rng(3).integers(0, V, 4)
    ids = np.tile(motif, (12, (T + 1) // 4 + 1))[:, :T + 1].astype(np.int32)
    before = obs.metrics.snapshot().get(
        "estimator.tokens_completed", {"value": 0.0})["value"]
    est, history = _session_fit(
        "hybridlm", ids, dict(optimizer=hybridlm_optimizer(3e-3), num_epochs=3,
                              seed=0), held_rows=4)
    assert history[-1]["eval_loss"] < history[0]["eval_loss"] - 0.1
    assert history[-1]["train_loss"] < history[0]["train_loss"]
    stats = est.fit_stats_
    assert stats["row_update"]["params"] == 0  # the dense step
    assert stats["steps"] == 3 * 4 and stats["steps_completed"] == 12
    snap = obs.metrics.snapshot()
    assert snap["estimator.tokens_completed"]["value"] - before == 12 * 2 * T
    assert snap["estimator.tokens_per_sec"]["value"] > 0
    facts = model().fit_facts(ids)
    for gauge, fact in (("layer_kinds.mamba", 2), ("layer_kinds.attention", 1),
                        ("ssd_chunk", 8), ("tokens_per_row", T),
                        ("ssd_flops_per_row", facts["ssd_flops_per_row"]),
                        ("flops_per_row", facts["flops_per_row"]),
                        ("remat_kept_bytes_per_row", 3 * T * 64 * 4)):
        assert snap[f"model.{gauge}"]["value"] == fact, gauge
    compiles = [r for r in est.last_fit_records_
                if r["name"] == "estimator.compile"]
    step_programs = [r for r in compiles if r["args"].get("what") == "4"]
    assert step_programs, [r["args"] for r in compiles]  # one scan of 4 steps
    assert step_programs[0]["args"]["layer_kinds"] == "mamba,attention,mamba"
    assert step_programs[0]["args"]["remat_keeps"] == "mlp_out"
    # tokens and FLOPs are the model's own word, no probe compiled
    assert not [r for r in compiles if r["args"].get("what") == "flops_probe"]
    assert stats["flops_per_step"] == 2 * facts["flops_per_row"]
    assert est.predict(ids[:2, :-1]).shape == (2, T, V)


def _replay(ids, order, seed=5, **changed):
    """The reference's epoch: its gradients through its AdamW, batch after
    batch in ``order``: (mean loss, initial leaves, final leaves)."""
    start = model().init(jax.random.PRNGKey(seed), ids[:2], None, method="loss")
    treedef = jax.tree.structure(start)
    first = [np.asarray(a) for a in jax.tree.leaves(start)]
    leaves = [a.copy() for a in first]  # the reference's AdamW works in place
    state, losses = ref.adamw_init(leaves), []
    hyper = {**HYPER, **changed}
    for i in range(0, len(order), 2):
        value, _, grads = ref.loss_and_grads(
            jax.tree.unflatten(treedef, leaves), ids[order[i:i + 2]], CFG)
        losses.append(float(value))
        leaves, state = ref.adamw_step(
            leaves, [np.asarray(g) for g in jax.tree.leaves(grads)], state,
            hyper["learning_rate"], hyper["b1"], hyper["b2"],
            hyper["weight_decay"])
    return float(np.mean(losses)), first, leaves


def _change_gap(got, first, want):
    return max(
        float(np.linalg.norm((g - z) - (w - z)) / np.linalg.norm(w - z))
        for g, z, w in zip(got, first, want))


@pytest.fixture(scope="module")
def fitted_epoch():
    ids = np.random.default_rng(7).integers(0, V, (4, T + 1)).astype(np.int32)
    est, history = _session_fit(
        "hybridlm-step", ids, dict(optimizer=hybridlm_optimizer(**HYPER),
                                   num_epochs=1, seed=5))
    assert est.fit_stats_["steps"] == 2
    return ids, est, history[0]["train_loss"], [
        np.asarray(a) for a in jax.tree.leaves(est.get_model().params)]


def test_the_epoch_program_is_the_references_epoch(fitted_epoch):
    """What the timed path itself produces (make_train_step in the scan
    runner, donation, hybridlm_optimizer, the epoch's order) against the
    reference's gradients through the reference's float32 AdamW."""
    ids, est, loss, got = fitted_epoch
    order = est.epoch_order(0, len(ids))
    ref_loss, first, want = _replay(ids, order)
    assert abs(loss - ref_loss) <= 1e-5
    assert _change_gap(got, first, want) <= 2e-3


@pytest.mark.parametrize("wrong", [
    {"learning_rate": 3.3e-4}, {"weight_decay": 0.0}, "order"])
def test_a_wrong_update_fails_the_comparison(fitted_epoch, wrong):
    ids, est, _, got = fitted_epoch
    order = est.epoch_order(0, len(ids))
    if wrong == "order":
        _, first, want = _replay(ids, order[::-1])
    else:
        _, first, want = _replay(ids, order, **wrong)
    assert _change_gap(got, first, want) > 4e-3


# -- the fourth family: gated delta-rule layers beside full attention (ISSUE 45) --
# model_type olmo_hybrid at the REHEARSAL's size of its configuration file:
# against benchmark/reference/olmo_hybrid.py (the per-token recurrence), the
# mutations the comparison must catch, the share of the heads, from_config

from benchmark.harness import cells as _cells  # noqa: E402
from benchmark.reference import olmo_hybrid as delta_ref  # noqa: E402
from raydp_tpu.ops import delta_rule  # noqa: E402


def _delta_config(**changed):
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        return {**_cells.sized(json.load(f), True), **changed}


DELTA_CONFIG = _delta_config()
DELTA_CFG = delta_ref.config_of(DELTA_CONFIG)


def delta_model(cls=HybridLM, config=DELTA_CONFIG, **kw):
    return cls.from_config(config, **{"dtype": jnp.float32, "loss_chunk": 16,
                                      "attn_impl": "full", **kw})


@pytest.fixture(scope="module")
def delta_params(batch):
    """Seeded parameters with every norm gain moved off 1, so that dropping
    or misplacing one shows."""
    p = delta_model().init(jax.random.PRNGKey(0), batch, None, method="loss")
    flat = jax.tree_util.tree_leaves_with_path(p)
    keys = jax.random.split(jax.random.PRNGKey(2), len(flat))
    moved = [leaf + 0.1 * jax.random.normal(k, leaf.shape)
             if path[-1].key in ("gate_norm", "norm1", "norm2", "q_norm",
                                 "k_norm", "final_norm")
             else leaf for (path, leaf), k in zip(flat, keys)]
    return jax.tree.unflatten(jax.tree.structure(p), moved)


@pytest.fixture(scope="module")
def delta_want(delta_params, batch):
    value, _, grads = jax.jit(
        lambda p, x: delta_ref.loss_and_grads(p, x, DELTA_CFG))(
            delta_params, batch)
    return float(value), jax.tree.leaves(grads), jax.jit(
        lambda p, x: delta_ref.forward(p, x, DELTA_CFG))(
            delta_params, batch[:, :-1])


@pytest.mark.parametrize("attn_impl, remat", [
    ("full", False), ("full", True), ("flash", True)])
def test_delta_system_against_the_reference(delta_params, batch, delta_want,
                                            attn_impl, remat):
    """Loss, logits and every gradient, float32, the chunked form (one
    chunk of 32 tokens here; tests/test_delta_rule.py holds several) against
    the reference's per-token recurrence."""
    m = delta_model(attn_impl=attn_impl, remat=remat)
    assert m.layer_types == ("delta", "delta", "delta", "attention")
    assert max(gaps(m, delta_params, batch, delta_want)) <= 2e-5


nn_silu = jax.nn.silu


class _NoL2(HybridLM):
    def _delta_l2(self, x):
        return x


class _SigmoidGate(HybridLM):
    def _delta_act(self, x, where):
        return jax.nn.sigmoid(x) if where == "gate" else nn_silu(x)


class _NoConvSilu(HybridLM):
    def _delta_act(self, x, where):
        return x if where == "conv" else nn_silu(x)


def _erase_after_write(q, k, v, log_alpha, beta, chunk=None):
    """The recurrence with the erase applied to the state AFTER the write."""
    def token(state, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        state = jnp.exp(a_t)[..., None, None] * state
        state = state + (b_t[..., None] * v_t)[..., None] * k_t[:, :, None, :]
        held = jnp.einsum("bhvd,bhd->bhv", state, k_t)
        state = state - (b_t[..., None] * held)[..., None] * k_t[:, :, None, :]
        return state, jnp.einsum("bhvd,bhd->bhv", state, q_t)

    b, _, h, dk = q.shape
    _, o = jax.lax.scan(
        token, jnp.zeros((b, h, v.shape[-1], dk), jnp.float32),
        tuple(z.swapaxes(0, 1) for z in (q, k, v, log_alpha, beta)))
    return o.swapaxes(0, 1)


def _scan_given(change):
    """``ops.delta_rule.gated_delta_rule`` with its arguments changed."""
    true = delta_rule.gated_delta_rule
    return lambda q, k, v, log_alpha, beta, **kw: true(
        *change(q, k, v, log_alpha, beta), **kw)


DELTA_MUTATIONS = {
    # name: (model class, fields changed, gated_delta_rule in its place)
    "beta_not_doubled": (HybridLM, {}, _scan_given(
        lambda q, k, v, a, b: (q, k, v, a, b / 2))),
    "q_not_scaled": (HybridLM, {}, _scan_given(
        lambda q, k, v, a, b: (q * q.shape[-1] ** 0.5, k, v, a, b))),
    "l2_norms_dropped": (_NoL2, {}, None),
    "gate_sigmoid_for_silu": (_SigmoidGate, {}, None),
    "conv_silu_dropped": (_NoConvSilu, {}, None),
    "decay_sign": (HybridLM, {}, _scan_given(
        lambda q, k, v, a, b: (q, k, v, -a, b))),
    "erase_after_write": (HybridLM, {}, _erase_after_write),
    "pre_norm_for_post_norm": (HybridLM, {"norm_placement": "pre"}, None),
}


@pytest.mark.parametrize("mutation", sorted(DELTA_MUTATIONS))
def test_a_delta_mutation_fails_the_comparison(delta_params, batch, delta_want,
                                               mutation, monkeypatch):
    """Each moves one of the matched readings (loss, logits, a gradient) by
    1e-3 and more, a hundred times the true model's gap."""
    cls, fields, scan = DELTA_MUTATIONS[mutation]
    if scan is not None:
        monkeypatch.setattr(delta_rule, "gated_delta_rule", scan)
    m = delta_model(cls, **fields)
    assert max(gaps(m, delta_params, batch, delta_want)) >= 1e-3, mutation


def _columns(w, names, first, count, width):
    """The parameters ``names`` of a mixer cut to heads ``first`` ..
    ``first + count - 1`` of ``width`` columns (or rows, for ``wo``) each."""
    cut = slice(first * width, (first + count) * width)
    return {name: (w[name][cut] if name == "wo" else w[name][..., cut])
            for name in names}


def test_the_two_head_shares_of_a_delta_layer_add_up_to_the_uncut_layer(batch):
    """THE SHARE: the mixer built with all 4 heads against the sum of the
    two chips' mixers of 2 heads each, every held head's columns of W_q, W_k,
    W_v, W_g, W_a, W_b, its convolution channels, its A_log and dt_bias and
    its rows of W_o cut from the uncut layer's. Everything between the
    projections and W_o works a head at a time (the convolution a channel,
    the l2 norms, beta, the decay, the state, the read-out norm with its one
    gain of Dv), so the parts add up exactly as W_o's rows do."""
    whole = delta_model(config=_delta_config(
        linear_num_key_heads=4, linear_num_value_heads=4))
    half = delta_model()
    heads, dk, dv = 4, whole.delta_key_dim, whole.delta_value_dim
    p = whole.init(jax.random.PRNGKey(3), batch, None, method="loss")
    w = p["params"]["layer_0"]
    a = 0.5 * jax.random.normal(jax.random.PRNGKey(4), (2, T, 64))
    p_half = half.init(jax.random.PRNGKey(3), batch, None, method="loss")
    with jax.default_matmul_precision("highest"):
        want = whole.apply(p, w, a, method="_delta")
        parts = []
        for first in (0, 2):
            conv = jnp.concatenate([
                w["conv_w"][:, base + first * width:
                            base + (first + 2) * width]
                for base, width in ((0, dk), (heads * dk, dk),
                                    (2 * heads * dk, dv))], axis=-1)
            share = {**w, "conv_w": conv,
                     **_columns(w, ("wq", "wk"), first, 2, dk),
                     **_columns(w, ("wv", "wg", "wo"), first, 2, dv),
                     **_columns(w, ("wa", "wb", "A_log", "dt_bias"),
                                first, 2, 1)}
            parts.append(half.apply(p_half, share, a, method="_delta"))
    assert float(jnp.abs(parts[0]).max()) > 0 and not bool(
        jnp.allclose(parts[0], parts[1]))
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(parts[0] + parts[1] - want).max()) <= 1e-5 * scale


def test_the_two_head_shares_of_an_attention_layer_add_up_under_the_held_statistic(
        batch):
    """THE SHARE, the full-attention layer: each chip norms q and k over the
    columns IT HOLDS (the departure the configuration lists). The two
    shares' outputs add up to the uncut layer's when the uncut layer takes
    the q/k norms' statistic a share (over heads 0-1 and over heads 2-3),
    and do NOT when it takes it over the whole projection, as the published
    model does: that sum of squares lives on two chips."""
    from raydp_tpu.models.transformer import _attend

    half = delta_model()
    dh, d = half.head_dim, 64
    keys = jax.random.split(jax.random.PRNGKey(5), 8)
    w = {name: 0.3 * jax.random.normal(k, (d, 4 * dh)) for name, k in zip(
        ("wq", "wk", "wv"), keys)}
    w["wo"] = 0.3 * jax.random.normal(keys[3], (4 * dh, d))
    w["q_norm"] = 1.0 + 0.1 * jax.random.normal(keys[4], (4 * dh,))
    w["k_norm"] = 1.0 + 0.1 * jax.random.normal(keys[5], (4 * dh,))
    a = jax.random.normal(keys[6], (2, T, d))
    p = half.init(jax.random.PRNGKey(3), batch, None, method="loss")

    def uncut(groups):
        """All four heads; the norms' statistic over ``groups`` equal parts
        of the projection."""
        def normed(x, gain):
            b, t, width = x.shape
            x = x.reshape(b, t, groups, width // groups)
            x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                  + half.rms_eps)
            return x.reshape(b, t, width) * gain

        def split(z):
            return z.reshape(2, T, 4, dh).transpose(0, 2, 1, 3)

        q, k = normed(a @ w["wq"], w["q_norm"]), normed(a @ w["wk"], w["k_norm"])
        o = _attend(split(q), split(k), split(a @ w["wv"]), impl="full",
                    axis="sp", causal=True)
        return o.transpose(0, 2, 1, 3).reshape(2, T, 4 * dh) @ w["wo"]

    with jax.default_matmul_precision("highest"):
        parts = [half.apply(p, _columns(
            w, ("wq", "wk", "wv", "wo", "q_norm", "k_norm"), first, 2, dh), a,
            method="_attention") for first in (0, 2)]
        a_share, whole = uncut(2), uncut(1)
    scale = float(jnp.abs(whole).max())
    assert float(jnp.abs(parts[0] + parts[1] - a_share).max()) <= 1e-5 * scale
    assert float(jnp.abs(parts[0] + parts[1] - whole).max()) >= 1e-2 * scale


def test_the_delta_parameter_tree_is_the_stage_of_the_published_model(
        delta_params):
    """Layers 0-3 of the published pattern, the held heads' widths, the
    q/k norms' gains over the held projection, an untied head."""
    p = delta_params["params"]
    assert sorted(p) == ["embed", "final_norm", "head", "layer_0", "layer_1",
                         "layer_2", "layer_3"]
    shapes = {k: v.shape for k, v in p["layer_0"].items()}
    assert shapes == {
        "wq": (64, 16), "wk": (64, 16), "wv": (64, 32), "wg": (64, 32),
        "wa": (64, 2), "wb": (64, 2), "wo": (32, 64), "conv_w": (4, 64),
        "A_log": (2,), "dt_bias": (2,), "gate_norm": (16,),
        "norm1": (64,), "norm2": (64,), "w_in": (64, 192), "w_out": (96, 64)}
    assert {k: v.shape for k, v in p["layer_3"].items()} == {
        "wq": (64, 32), "wk": (64, 32), "wv": (64, 32), "wo": (32, 64),
        "q_norm": (32,), "k_norm": (32,), "norm1": (64,), "norm2": (64,),
        "w_in": (64, 192), "w_out": (96, 64)}
    assert p["head"].shape == (64, V) and p["embed"].shape == (V, 64)


@pytest.mark.parametrize("change, match", [
    ({"model_type": "olmo"}, "olmo_hybrid"),
    ({"tie_word_embeddings": True}, "tie_word_embeddings"),
    ({"attention_bias": True}, "attention_bias"),
    ({"linear_num_value_heads": 4}, "as many delta-rule key heads"),
    ({"linear_allow_neg_eigval": False}, "a step that may reflect"),
    ({"layer_types": ["linear_attention", "mamba"] * 4}, "layer_types gives"),
])
def test_from_config_refuses_what_the_delta_family_does_not_build(change, match):
    with pytest.raises(ValueError, match=match):
        HybridLM.from_config(_delta_config(**change))


def test_the_delta_familys_fields_follow_the_config():
    m = delta_model()
    assert (m.norm_placement, m.qk_norm, m.qk_norm_over, m.tied_head) == (
        "post", True, "projection", False)
    assert m.rope_theta == 0.0
    assert (m.delta_heads, m.delta_heads_total) == (2, 4)
    later = delta_model(config=_delta_config(
        num_hidden_layers=2, share={"first_layer": 2, "heads_total": 4}))
    assert later.layer_types == ("delta", "attention")
    with pytest.raises(ValueError, match="not a delta-rule layer's share"):
        delta_model(delta_heads_total=1).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 9), jnp.int32), None,
            method="loss")


def test_delta_fit_facts_say_what_a_row_holds():
    """At the published widths (ISSUE 45): three delta layers of 15 held
    heads of 30, chunk 64, the recurrence's 6 Dk Dv a token and head, the
    state a row carries; 766.2 M parameters' worth of FLOPs."""
    import json

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    big = HybridLM.from_config(config, **config["model"]["kwargs"])
    t = 8192
    facts = big.fit_facts(np.zeros((1, t + 1), np.int32))
    assert facts["layer_kinds"] == "delta,delta,delta,attention"
    assert (facts["layer_kinds.delta"], facts["layer_kinds.attention"],
            facts["layer_kinds.mamba"]) == (3, 1, 0)
    assert (facts["delta.heads_held"], facts["delta.heads_total"],
            facts["delta.chunk"]) == (15, 30, delta_rule.CHUNK)
    assert (facts["delta.decay"], facts["delta.scan"]) == ("head", "plain")
    assert facts["delta.flops_per_row"] == 3 * 3 * 6 * 96 * 192 * 15 * t
    assert facts["delta.state_bytes_per_row"] == 3 * 15 * 192 * 96 * 4
    linear = 3840 * (1440 + 1440 + 2880 + 2880 + 30) + 2880 * 3840
    full = 4 * 3840 * 1920
    swiglu = 3 * 3840 * 11008
    assert facts["flops_per_row"] == (
        6 * (3 * linear + full + 4 * swiglu + 3 * 4 * 5760) * t
        + facts["delta.flops_per_row"]
        + 12 * 1920 * (t * (t + 1) // 2) + 6 * 3840 * 12544 * t)
    # ISSUE 45's reckoning: 35 TFLOP a step
    assert facts["flops_per_row"] == pytest.approx(3.5e13, rel=0.05)
    assert facts["remat_keeps"] == "attn_out,attn_lse,mlp_out"
    assert facts["attention_backward"] == "global=fused"
    # a model without the mixer says nothing of it
    plain = HybridLM(vocab_size=V).fit_facts(np.zeros((1, 33), np.int32))
    assert not [k for k in plain if "delta" in k]


@pytest.mark.parametrize("sizes", ["published", "tiny"])
def test_the_delta_models_flops_are_the_benchmarks_count(sizes):
    import json

    from benchmark.harness import delta_costs

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    config, t = (config, 8192) if sizes == "published" else (DELTA_CONFIG, T)
    module = HybridLM.from_config(config)
    parts = delta_costs.step_flops(config, 1, t)
    assert module.flops_per_row_parts(t) == {
        "scan": 0,  # no state-space layer
        **{k: v for k, v in parts.items() if k != "total"}}
    assert module.fit_facts(np.zeros((1, t + 1), np.int32))[
        "flops_per_row"] == parts["total"]
    kernels = delta_costs.kernels(config, 1, t)
    heads, dk, dv = (config[k] for k in (
        "linear_num_key_heads", "linear_key_head_dim", "linear_value_head_dim"))
    rows = t * heads
    assert kernels["delta_fwd"] == {"layers": 3, "cost": {
        "flops": rows * 6 * dk * dv,
        "bytes": rows * (2 * dk + 2 * dv + 2) * 2}}
    assert kernels["delta_bwd"] == {"layers": 3, "cost": {
        "flops": rows * 12 * dk * dv,
        "bytes": rows * (2 * dk + 2 * dv + 2 + 2 * dk + dv + 2) * 2}}


def test_estimator_fits_the_delta_family_and_sets_its_gauges():
    """The same call as every hybrid model's: the loss falls, and what the
    model says of its delta-rule layers is in the gauges."""
    from raydp_tpu import obs

    motif = np.random.default_rng(3).integers(0, V, 4)
    ids = np.tile(motif, (12, (T + 1) // 4 + 1))[:, :T + 1].astype(np.int32)
    module = delta_model()
    global model
    plain, model = model, lambda: module  # _session_fit builds ``model()``
    try:
        est, history = _session_fit(
            "deltahybridlm", ids, dict(optimizer=hybridlm_optimizer(3e-3),
                                       num_epochs=3, seed=0), held_rows=4)
    finally:
        model = plain
    assert history[-1]["eval_loss"] < history[0]["eval_loss"] - 0.1
    snap = obs.metrics.snapshot()
    facts = module.fit_facts(ids)
    for gauge, fact in (("layer_kinds.delta", 3), ("layer_kinds.attention", 1),
                        ("delta.heads_held", 2), ("delta.heads_total", 4),
                        ("delta.chunk", T),
                        ("delta.flops_per_row", facts["delta.flops_per_row"]),
                        ("delta.state_bytes_per_row", 3 * 2 * 16 * 8 * 4),
                        ("flops_per_row", facts["flops_per_row"])):
        assert snap[f"model.{gauge}"]["value"] == fact, gauge
    compiles = [r for r in est.last_fit_records_
                if r["name"] == "estimator.compile"]
    assert [r for r in compiles if r["args"].get("layer_kinds")
            == "delta,delta,delta,attention"]
