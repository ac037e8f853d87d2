"""The looped LM (models/looplm.py) at rehearsal sizes, float32, seeded random
weights: against the plain reference (benchmark/reference/ouro.py), its
sharing of one parameter tree over the loop steps, its scanned loop against
the loop written out, its chunked exit loss; the sequence column through the
exchange; and a JaxEstimator fit on a frame that carries it, whose epoch
program is held to the reference's gradients through the reference's AdamW."""

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

from benchmark.reference import ouro as ref  # noqa: E402
from raydp_tpu.models import LoopLM, looplm_optimizer  # noqa: E402
from raydp_tpu.models.looplm import exit_mass  # noqa: E402

V, D, H, L, F, R, T = 256, 64, 4, 2, 176, 4, 32
CFG = {"num_attention_heads": H, "rope_theta": 1e6, "rms_norm_eps": 1e-6,
       "total_ut_steps": R, "entropy_beta": 0.1}


def model(**kw):
    args = dict(vocab_size=V, hidden_size=D, num_heads=H, num_layers=L,
                intermediate_size=F, loop_steps=R, dtype=jnp.float32,
                loss_chunk=16)
    args.update(kw)
    return LoopLM(**args)


@pytest.fixture(scope="module")
def batch():
    return jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0, V)


@pytest.fixture(scope="module")
def params(batch):
    return model().init(jax.random.PRNGKey(0), batch, None, method="loss")


def program(m, p, x):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(
            lambda q: m.apply(q, x, method="loss"), has_aux=True)(p)


def leaves_close(got, want, rel):
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        gap = float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-20))
        assert gap <= rel, (jax.tree_util.keystr(path), gap)


def test_one_tree_of_L_layers_whatever_the_loop_count(params):
    names = sorted(params["params"])
    assert [n for n in names if n.startswith("layer_")] == ["layer_0", "layer_1"]
    one = model(loop_steps=1).init(
        jax.random.PRNGKey(0), jnp.zeros((2, T + 1), jnp.int32), None,
        method="loss")
    assert jax.tree.map(jnp.shape, one) == jax.tree.map(jnp.shape, params)


@pytest.mark.parametrize("what", ["logits", "gates", "loss", "gradients"])
def test_system_against_the_reference(params, batch, what):
    m = model()
    if what in ("logits", "gates"):
        with jax.default_matmul_precision("highest"):
            z, lam = m.apply(params, batch[:, :-1], method="exits")
        z_ref, lam_ref = ref.forward(params, batch[:, :-1], CFG)
        if what == "logits":
            assert z.shape == (R, 2, T, V)
            for t in range(R):  # each exit's logits
                assert float(jnp.abs(z[t] - z_ref[t]).max()) <= 1e-5 * float(
                    jnp.abs(z_ref[t]).max())
        else:
            np.testing.assert_allclose(lam, lam_ref, atol=1e-6)
            np.testing.assert_allclose(
                exit_mass(lam), jnp.stack(ref.exit_distribution(list(lam_ref))),
                atol=1e-6)
        return
    (loss, aux), grads = program(m, params, batch)
    loss_ref, aux_ref, grads_ref = ref.loss_and_grads(
        params, batch, CFG, token_block=16, checkpoint=True)
    if what == "loss":
        assert abs(float(loss) - float(loss_ref)) <= 2e-6
        for key in ("exit_loss", "exit_mass"):
            np.testing.assert_allclose(aux[key], aux_ref[key], atol=2e-6)
        assert abs(float(aux["exit_mass"].sum()) - 1.0) <= 1e-6
    else:
        leaves_close(grads, grads_ref, 1e-5)


def test_a_dropped_loop_step_or_an_unshared_weight_fails_the_comparison(
        params, batch):
    (loss, _), grads = program(model(), params, batch)
    (short, _), _ = program(model(loop_steps=R - 1), params, batch)
    assert abs(float(short) - float(loss)) > 1e-4
    other = jax.tree.map(lambda a: a, params)
    other["params"]["layer_0"] = jax.tree.map(
        lambda a: a * 1.01, params["params"]["layer_0"])
    (moved, _), _ = program(model(), other, batch)
    assert abs(float(moved) - float(loss)) > 1e-5


def test_one_loop_step_is_a_plain_decoder(params, batch):
    """total_ut_steps = 1: the one exit takes all the mass (its gate is not
    used), the entropy term is zero, and the model is the plain decoder of
    the same layers: embedding, L blocks, final norm, head, cross-entropy."""
    (loss, aux), _ = program(model(loop_steps=1), params, batch)
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        h = p["embed"][batch[:, :-1]]
        for i in range(L):
            h = ref._block(p[f"layer_{i}"], h, CFG)
        z = ref._rms(h, p["final_norm"], 1e-6) @ p["head"]
        ce = -jnp.take_along_axis(
            jax.nn.log_softmax(z, -1), batch[:, 1:, None], -1)[..., 0]
    assert abs(float(loss) - float(ce.mean())) <= 2e-6
    np.testing.assert_allclose(aux["exit_mass"], [1.0], atol=1e-7)


def test_shared_gradient_is_the_sum_over_untied_copies(params, batch):
    """R untied copies of the L layers set to the same values: the shared
    layers' gradient is the sum of the copies' gradients."""
    p = params["params"]
    copies = [[p[f"layer_{i}"] for i in range(L)] for _ in range(R)]

    def untied_loss(copies):
        with jax.default_matmul_precision("highest"):
            h = p["embed"][batch[:, :-1]]
            hs, lams = [], []
            for step in copies:
                for w in step:
                    h = ref._block(w, h, CFG)
                h = ref._rms(h, p["final_norm"], 1e-6)
                hs.append(h)
                lams.append(jax.nn.sigmoid(h @ p["gate"]["w"] + p["gate"]["b"]))
        ps = ref.exit_distribution(lams)
        ces = [ref._cross_entropy(params, h, batch[:, 1:], 0, jnp.float32)
               for h in hs]
        ent = -sum(q * jnp.log(jnp.maximum(q, 1e-30)) for q in ps)
        return (jnp.mean(sum(q * c for q, c in zip(ps, ces)))
                - CFG["entropy_beta"] * jnp.mean(ent))

    per_copy = jax.grad(untied_loss)(copies)
    _, grads = program(model(), params, batch)
    for i in range(L):
        summed = jax.tree.map(lambda *g: sum(g), *[per_copy[t][i] for t in range(R)])
        leaves_close(grads["params"][f"layer_{i}"], summed, 1e-5)
        one = per_copy[0][i]["wq"]
        assert float(jnp.linalg.norm(summed["wq"] - one)) > 1e-3 * float(
            jnp.linalg.norm(one))


FLASH = {"attn_impl": "flash"}


@pytest.mark.parametrize("base, variant", [
    ({}, {"loss_chunk": 0}), ({}, {"remat": False}),
    ({}, {"loss_chunk": 0, "remat": False}), ({}, FLASH),
    (FLASH, {**FLASH, "remat": False}),
    (FLASH, {**FLASH, "loss_chunk": 0, "remat": False}),
])
def test_forms_agree(params, batch, base, variant):
    """The chunked exit loss and the whole one, recomputation on (with what
    its policy keeps: ``mlp_out`` in the plain model, the kernel's output and
    log-sum-exp too in the flash one) and off, the flash kernel (interpreted
    here) and the plain attention: one arithmetic."""
    (loss, aux), grads = program(model(**base), params, batch)
    (loss2, aux2), grads2 = program(model(**variant), params, batch)
    tight = base.get("attn_impl") == variant.get("attn_impl")
    assert abs(float(loss) - float(loss2)) <= (1e-6 if tight else 1e-5)
    np.testing.assert_allclose(aux["exit_loss"], aux2["exit_loss"], atol=1e-5)
    leaves_close(grads2, grads, 1e-5 if tight else 1e-4)


def _pallas_calls(jaxpr, name):
    """{path: count} of the pallas calls called ``name`` in a jaxpr, by the
    ``scan`` equations they lie under: ``("scan1",)`` is the second scan of
    the top level (a gradient has the forward sweep over the loop steps in
    one scan and the backward sweep in a later one)."""
    found = {}

    def walk(jaxpr, path):
        scans = 0
        for eqn in jaxpr.eqns:
            here = path
            if eqn.primitive.name == "scan":
                here = path + (f"scan{scans}",)
                scans += 1
            if eqn.primitive.name == "pallas_call" and name in eqn.params["name"]:
                found[path] = found.get(path, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)

    walk(jaxpr, ())
    return found


@pytest.mark.parametrize("remat", [True, False])
def test_the_backward_pass_runs_no_flash_forward(params, batch, remat):
    """What the policy is for: the gradient's forward scan (over the loop
    steps) holds one ``flash_attention_fwd`` call a layer, and its backward
    scan none: the kernel's output and log-sum-exp are kept and the
    recomputed call is dead code. Without ``remat`` nothing is recomputed,
    so the counts are the same; a bare ``jax.checkpoint`` had one a layer in
    each scan."""
    m = model(attn_impl="flash", remat=remat)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: m.apply(p, batch, method="loss")[0]))(params)
    forward = _pallas_calls(jaxpr.jaxpr, "flash_attention_fwd")
    # the backward pass is ONE call a layer since PR 43
    backward = _pallas_calls(jaxpr.jaxpr, "flash_attention_bwd_dq_dkv")
    assert not _pallas_calls(jaxpr.jaxpr, "flash_attention_bwd_dkv")
    # one scan holds every forward call, another every backward one
    assert list(forward.values()) == [L], forward
    assert list(backward.values()) == [L], backward
    assert set(forward) != set(backward)


@pytest.mark.parametrize("what", ["states", "mass", "loss"])
def test_the_scanned_loop_is_the_loop_written_out(params, batch, what):
    """``loss`` runs the loop as a ``lax.scan`` (the one form the step
    program has); ``hidden_states`` and the reference write it as a Python
    ``for``. What the scan held at every loop step is what they compute."""
    m = model()
    with jax.default_matmul_precision("highest"):
        loss, aux = m.apply(params, batch, None, True, method="loss")
        hs, lam = m.apply(params, batch[:, :-1], method="hidden_states")
    ref_loss, ref_aux = ref.loss(params, batch, CFG, with_states=True)
    if what == "states":
        assert aux["hidden"].shape == (R, 2, T, D)
        np.testing.assert_allclose(aux["hidden"], hs, atol=1e-5)
        np.testing.assert_allclose(aux["hidden"], ref_aux["hidden"], atol=1e-5)
    elif what == "mass":
        np.testing.assert_allclose(aux["mass"], exit_mass(lam), atol=1e-6)
        np.testing.assert_allclose(aux["mass"], ref_aux["mass"], atol=1e-6)
        np.testing.assert_allclose(aux["mass"].sum(0), 1.0, atol=1e-6)
    else:
        assert abs(float(loss) - float(ref_loss)) <= 2e-6
        plain, plain_aux = m.apply(params, batch, method="loss")
        assert float(plain) == float(loss) and set(plain_aux) == {
            "exit_loss", "exit_mass"}


def test_fit_facts_say_what_a_row_holds():
    """Tokens and model FLOPs of a row come from the model, from a sample of
    the staged feature: hand-worked at the published widths (ISSUE 27)."""
    big = LoopLM(vocab_size=49152)  # the defaults are the published widths
    facts = big.fit_facts(np.zeros((2, 4097), np.int32))
    assert facts["tokens_per_row"] == 4096
    assert facts["loop_steps"] == 4 and facts["layer_applications_per_step"] == 24
    assert facts["loop"] == "scan" and facts["remat"] is True
    layer = 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert facts["flops_per_row"] == (
        6 * layer * 4096 * 24 + 6 * 2048 * 49152 * 4096 * 4
        + 12 * 2048 * (4096 * 4097 // 2) * 24)
    assert 2 * facts["flops_per_row"] == pytest.approx(9.03e13, rel=2e-3)
    assert not hasattr(big, "loop")  # one form, no knob
    # the loss's gradient is taken in the forward sweep: three products over
    # the vocabulary a chunk, not the four of a recomputed chunk (ISSUE 32)
    assert (facts["loss_grad"], facts["loss_products_per_chunk"]) == ("forward", 3)
    # what a recomputed block keeps, a row and all 24 block applications:
    # bf16 [4096, 2048] twice (the flash output, w_down's output) and the
    # float32 log-sum-exp of 16 heads x 4096 rows; the plain attention names
    # nothing, and without remat nothing is recomputed
    wide, lse = 4096 * 2048 * 2, 16 * 4096 * 4
    assert (facts["remat_keeps"], facts["remat_kept_bytes_per_row"]) == (
        "mlp_out", 24 * wide) == ("mlp_out", 402_653_184)
    flash = big.clone(attn_impl="flash").fit_facts(np.zeros((2, 4097), np.int32))
    assert flash["remat_keeps"] == "attn_out,attn_lse,mlp_out"
    assert flash["remat_kept_bytes_per_row"] == 24 * (2 * wide + lse) == 811_597_824
    off = big.clone(remat=False).fit_facts(np.zeros((2, 4097), np.int32))
    assert (off["remat"], off["remat_keeps"], off["remat_kept_bytes_per_row"]) == (
        False, "", 0)


ATTENTION_FACTS = ("attention_backward", "attention.backward_fused_layers",
                   "attention.dq_resident_bytes")


@pytest.mark.parametrize("impl, tokens, want", [
    # every block application's backward is the one fused call; a head's
    # float32 dq [4096, 128] stays in VMEM
    ("flash", 4096, ("global=fused", 24, 4096 * 128 * 4)),
    ("ulysses_flash", 4096, ("global=fused", 24, 4096 * 128 * 4)),
    # a ring step's offsets are values of the program
    ("ring_flash", 4096, ("global=two_call", 0, 0)),
    # 128k tokens: a dq of 64 MB is past what a call may ask for
    ("flash", 131072, ("global=two_call", 0, 0)),
    # no flash kernel: autodiff's backward
    ("full", 4096, ("global=xla", 0, 0))])
def test_fit_facts_say_which_form_the_attention_backward_takes(
        impl, tokens, want):
    """PR 43: from ``attn_impl`` and the shapes, as the kernel decides it
    (``ops.flash_attention.backward_form``); numbers become gauges
    ``model.attention.*``."""
    big = LoopLM(vocab_size=49152, attn_impl=impl)
    facts = big.fit_facts(np.zeros((2, tokens + 1), np.int32))
    assert tuple(facts[name] for name in ATTENTION_FACTS) == want


@pytest.mark.parametrize("impl, tokens, want", [
    # ISSUE 54: a head's causal calls step over the 10 tiles under the
    # diagonal of 4 x 4 (1024-row tiles), not over the 16 of the rectangle
    ("flash", 4096, ("global=live", 100.0)),
    ("ulysses_flash", 4096, ("global=live", 100.0)),
    # a ring step's offsets are values of the program: no grid follows them
    ("ring_flash", 4096, ("global=rectangular:runtime offsets", 62.5)),
    ("ring_flash", 16384, ("global=rectangular:runtime offsets", 53.125)),
    # no flash kernel, no grid
    ("full", 4096, ("global=xla", None))])
def test_fit_facts_say_which_grid_the_attention_calls_step_over(
        impl, tokens, want):
    """ISSUE 54: from ``attn_impl`` and the shapes, as the kernel decides it
    (``ops.flash_attention.causal_grid``); the share becomes the gauge
    ``model.attention.causal_grid_live_share``."""
    big = LoopLM(vocab_size=49152, attn_impl=impl)
    facts = big.fit_facts(np.zeros((2, tokens + 1), np.int32))
    assert (facts["attention_grid"],
            facts.get("attention.causal_grid_live_share")) == want


# -- the sequence column ------------------------------------------------------


def sequence_table(rows, width, seed=0):
    ids = np.random.default_rng(seed).integers(0, V, (rows, width)).astype(np.int32)
    return pa.table({
        "tokens": pa.FixedSizeListArray.from_arrays(pa.array(ids.ravel()), width),
        "weight": pa.array(np.arange(rows, dtype=np.float32)),
    }), ids


@pytest.mark.parametrize("columns, want_width", [
    (["tokens"], T + 1), (["weight", "tokens"], T + 2)])
def test_fixed_size_list_column_stages_as_a_matrix(columns, want_width):
    from raydp_tpu.exchange.dataset import _table_to_numpy

    table, ids = sequence_table(5, T + 1)
    table = pa.concat_tables([table.slice(0, 2), table.slice(2)])  # two chunks
    x, y = _table_to_numpy(table, columns, None, np.int32, np.float32)
    assert y is None and x.dtype == np.int32 and x.shape == (5, want_width)
    np.testing.assert_array_equal(x[:, -(T + 1):], ids)
    if len(columns) == 2:
        np.testing.assert_array_equal(x[:, 0], np.arange(5))


def test_null_sequence_rows_fail_loudly():
    from raydp_tpu.exchange.dataset import _table_to_numpy

    column = pa.array([[1, 2], None], pa.list_(pa.int32(), 2))
    with pytest.raises(ValueError, match="null rows"):
        _table_to_numpy(pa.table({"tokens": column}), ["tokens"], None,
                        np.int32, np.float32)


# -- through JaxEstimator -----------------------------------------------------


def test_estimator_fit_on_an_etl_frame_with_the_sequence_column():
    """ETL -> store -> exchange -> JaxEstimator.fit, the model's own loss, no
    label column: the loss falls, the step is the dense one, the resident
    scan runner ran, tokens are counted, the evaluation reports the exits."""
    from jax.sharding import Mesh

    import raydp_tpu
    from raydp_tpu import obs
    from raydp_tpu.cluster import api as cluster
    from raydp_tpu.estimator import JaxEstimator

    # learnable rows: every sequence repeats one short motif
    rng = np.random.default_rng(3)
    motif = rng.integers(0, V, 4)
    ids = np.tile(motif, (12, (T + 1) // 4 + 1))[:, :T + 1].astype(np.int32)
    table = pa.table({"tokens": pa.FixedSizeListArray.from_arrays(
        pa.array(ids.ravel()), T + 1)})
    session = raydp_tpu.init_etl("looplm", num_executors=1, executor_cores=1,
                                 executor_memory="500M")
    try:
        df = session.from_arrow(table, num_partitions=2)
        train, held = df.limit(8), df.limit(4)
        before = obs.metrics.snapshot().get(
            "estimator.tokens_completed", {"value": 0.0})["value"]
        est = JaxEstimator(
            model=model(), optimizer=looplm_optimizer(3e-3), loss="model",
            feature_columns=["tokens"], feature_dtype=np.int32,
            label_column=None, batch_size=2, num_epochs=3, seed=0,
            # one device: the resident scan runner (the suite's default is a
            # data mesh over its 8 virtual devices)
            mesh=Mesh(np.array(jax.devices()[:1]), ("data",)))
        history = est.fit_on_etl(train, held)
    finally:
        raydp_tpu.stop_etl()
        cluster.shutdown()
    assert history[-1]["eval_loss"] < history[0]["eval_loss"] - 0.1
    assert history[-1]["train_loss"] < history[0]["train_loss"]
    assert len(history[-1]["eval_exit_loss"]) == R
    assert abs(sum(history[-1]["eval_exit_mass"]) - 1.0) < 1e-5
    stats = est.fit_stats_
    assert stats["row_update"]["params"] == 0  # the dense step
    assert stats["row_update"]["write_back"] == {
        "kernel": 0, "scatter": 0, "reason": ""}  # and no row to write back
    assert stats["steps"] == 3 * 4 and stats["steps_completed"] == 12
    snap = obs.metrics.snapshot()
    assert snap["estimator.tokens_completed"]["value"] - before == 12 * 2 * T
    assert snap["estimator.tokens_per_sec"]["value"] > 0
    assert snap["model.loop_steps"]["value"] == R
    assert snap["model.layer_applications_per_step"]["value"] == R * L
    assert snap["estimator.eval.exit_loss.0"]["value"] == pytest.approx(
        history[-1]["eval_exit_loss"][0])
    compiles = [r for r in est.last_fit_records_
                if r["name"] == "estimator.compile"]
    step_programs = [r for r in compiles if r["args"].get("what") == "4"]
    assert step_programs, [r["args"] for r in compiles]  # one scan of 4 steps
    assert step_programs[0]["args"]["loop"] == "scan"
    assert step_programs[0]["args"]["remat"] is True
    assert step_programs[0]["args"]["loss_grad"] == "forward"
    assert snap["model.loss_products_per_chunk"]["value"] == 3
    # what the recomputed blocks keep (the plain attention names nothing):
    # float32 [T, D] of w_down's output, a row and R x L block applications
    assert step_programs[0]["args"]["remat_keeps"] == "mlp_out"
    assert snap["model.remat_kept_bytes_per_row"]["value"] == R * L * T * D * 4
    # tokens and FLOPs are the model's own word (fit_facts), no probe compiled
    assert snap["model.tokens_per_row"]["value"] == T
    assert not [r for r in compiles if r["args"].get("what") == "flops_probe"]
    assert stats["flops_per_step"] == 2 * model().fit_facts(ids)["flops_per_row"]
    # the last exit's logits, for whoever predicts with the fitted model
    assert est.predict(ids[:2, :-1]).shape == (2, T, V)


HYPER = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "weight_decay": 0.1}


def _one_epoch(optimizer, ids, seed=5):
    """One epoch of a JaxEstimator fit (the resident scan runner's compiled
    epoch program, donation and all) at float32 / highest on a frame that
    came through the ETL: (estimator, mean training loss, fitted leaves)."""
    from jax.sharding import Mesh

    import raydp_tpu
    from raydp_tpu.cluster import api as cluster
    from raydp_tpu.estimator import JaxEstimator

    table = pa.table({"tokens": pa.FixedSizeListArray.from_arrays(
        pa.array(ids.ravel()), ids.shape[1])})
    session = raydp_tpu.init_etl("looplm-step", num_executors=1,
                                 executor_cores=1, executor_memory="500M")
    try:
        est = JaxEstimator(
            model=model(), optimizer=optimizer, loss="model",
            feature_columns=["tokens"], feature_dtype=np.int32,
            label_column=None, batch_size=2, num_epochs=1, seed=seed,
            mesh=Mesh(np.array(jax.devices()[:1]), ("data",)))
        with jax.default_matmul_precision("highest"):
            history = est.fit_on_etl(session.from_arrow(table, num_partitions=1))
    finally:
        raydp_tpu.stop_etl()
        cluster.shutdown()
    assert est.fit_stats_["steps"] == len(ids) // 2
    return est, history[0]["train_loss"], [
        np.asarray(a) for a in jax.tree.leaves(est.get_model().params)]


def _replay(ids, order, seed=5, **changed):
    """The reference's epoch: its gradients through its AdamW, batch after
    batch in ``order``: (mean loss, initial leaves, final leaves)."""
    start = model().init(jax.random.PRNGKey(seed), ids[:2], None, method="loss")
    treedef = jax.tree.structure(start)
    leaves = first = [np.asarray(a) for a in jax.tree.leaves(start)]
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
    """Worst parameter: the L2 distance of its change from the reference's
    change, over the reference's change."""
    return max(
        float(np.linalg.norm((g - z) - (w - z)) / np.linalg.norm(w - z))
        for g, z, w in zip(got, first, want) if g.ndim)


@pytest.fixture(scope="module")
def fitted_epoch():
    ids = np.random.default_rng(7).integers(0, V, (4, T + 1)).astype(np.int32)
    est, loss, got = _one_epoch(looplm_optimizer(**HYPER), ids)
    return ids, est, loss, got


def test_the_epoch_program_is_the_references_epoch(fitted_epoch):
    """What the timed path itself produces (make_train_step in the scan
    runner, donation, looplm_optimizer, the epoch's order) against the
    reference's gradients through the reference's float32 AdamW."""
    ids, est, loss, got = fitted_epoch
    order = est.epoch_order(0, len(ids))
    assert sorted(order.tolist()) == [0, 1, 2, 3]
    ref_loss, first, want = _replay(ids, order)
    assert abs(loss - ref_loss) <= 1e-5
    assert _change_gap(got, first, want) <= 1.5e-3  # 5.4e-4 here


@pytest.mark.parametrize("wrong", [
    {"b2": 0.999}, {"learning_rate": 3.3e-4}, {"weight_decay": 0.0},
    "order", "half_batch", "decay_on_every_leaf"])
def test_a_wrong_update_fails_the_comparison(fitted_epoch, wrong):
    """A wrong b2, rate or decay, the batches in another order, a step on
    half its batch, decay on the norm gains: each moves some parameter's
    change nine times and more as far as the right update stands from the
    reference (b2 and the decay, which two steps hardly show: 0.0066 and
    0.0049; the others 0.1 to 1)."""
    ids, est, _, got = fitted_epoch
    order = est.epoch_order(0, len(ids))
    if wrong == "decay_on_every_leaf":  # the program's side: no mask
        import optax

        _, _, got = _one_epoch(optax.adamw(
            HYPER["learning_rate"], b1=HYPER["b1"], b2=HYPER["b2"],
            weight_decay=HYPER["weight_decay"]), ids)
        _, first, want = _replay(ids, order)
    elif wrong == "order":
        _, first, want = _replay(ids, order[::-1])
    elif wrong == "half_batch":
        _, first, want = _replay(ids, order[[0, 0, 2, 2]])
    else:
        _, first, want = _replay(ids, order, **wrong)
    assert _change_gap(got, first, want) > 4e-3


def test_model_loss_refuses_what_it_cannot_mean():
    from raydp_tpu.estimator import JaxEstimator
    from raydp_tpu.estimator.jax_estimator import make_train_step

    est = JaxEstimator(model=model(), loss="model", metrics=["accuracy"])
    with pytest.raises(ValueError, match="metrics are functions of a prediction"):
        est._make_eval_step(model(), "model")
    with pytest.raises(ValueError, match="row-wise update"):
        make_train_step(model(), "model", looplm_optimizer(), row_paths=(("a",),))
