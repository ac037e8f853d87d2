"""Row-wise optimizer update (estimator/row_update.py): the step that
differentiates and updates only the rows a batch read is the dense step's
mathematics where it engages, and the dense step itself where it does not."""

import re
import tempfile

import numpy as np
import pytest

from raydp_tpu.estimator import JaxEstimator, row_update
from raydp_tpu.estimator.jax_estimator import _LOSSES, make_train_step
from raydp_tpu.exchange import dataframe_to_dataset
from raydp_tpu.ops import backend
from tests.test_jax_estimator import criteo_df  # noqa: F401 - a fixture


@pytest.fixture(scope="module")
def session():
    """An ETL session under this module's own name: on one xdist worker right
    after tests/test_jax_estimator.py, whose session the same name stops, the
    head can still hold that name ('already taken', every test here an
    error)."""
    import raydp_tpu

    s = raydp_tpu.init_etl("test-row-update", num_executors=2,
                           executor_cores=1, executor_memory="300M")
    yield s
    raydp_tpu.stop_etl()


BATCH = 32
# two tables above the shape rule (32 rows to a row of the batch), three
# below it, one of them of three rows
VOCABS = (5000, 7, 300, 2000, 3)
ROW_PATHS = (("params", "embedding_0"), ("params", "embedding_3"))


def _dlrm(vocabs=VOCABS, num_dense=4, kernel=False):
    from raydp_tpu.models import DLRM

    return DLRM(vocab_sizes=tuple(vocabs), num_dense=num_dense, embed_dim=8,
                bottom_mlp=(16, 8), top_mlp=(16, 8),
                use_pallas_interaction=kernel)


def _batches(n, seed=0):
    """Batches with many repeated ids, and ids at ``vocab - 1``."""
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    for _ in range(n):
        ids = np.stack(
            [np.where(rng.random(BATCH) < 0.25, v - 1,
                      rng.integers(0, min(v, 12), BATCH)) for v in VOCABS], 1)
        yield (
            (jnp.asarray(rng.normal(size=(BATCH, 4)), jnp.float32),
             jnp.asarray(ids, jnp.int32)),
            jnp.asarray(rng.random(BATCH) < 0.5, jnp.float32),
        )


def _optimizers():
    import optax

    from raydp_tpu.models import dlrm_optimizer

    return {
        "adagrad": lambda: optax.adagrad(0.05),
        "sgd": lambda: optax.sgd(0.05),
        "adam": lambda: optax.adam(1e-2),
        "adamw": lambda: optax.adamw(1e-2),
        "dlrm_optimizer": dlrm_optimizer,
        "clip+adagrad": lambda: optax.chain(
            optax.clip_by_global_norm(1.0), optax.adagrad(0.05)),
        # what these do depends on the step's number, which two observed
        # steps cannot show: apply_every(3) pays the accumulated updates out
        # on every third step, to the rows of that step's batch alone on the
        # row path (9.9e-3 off the dense step after three steps, REVIEW of
        # PR 25), and a schedule may switch anything on at any step
        "adagrad+apply_every": lambda: optax.chain(
            optax.adagrad(0.05), optax.apply_every(3)),
        "adagrad+schedule": lambda: optax.adagrad(
            optax.linear_schedule(0.05, 0.01, 100)),
    }


@pytest.mark.parametrize("name, kernel", [
    ("adagrad", False), ("sgd", False), ("adagrad", True)])
def test_row_step_equals_dense_optax_steps(name, kernel):
    """(1) the estimator's step on the row path against plain optax dense
    steps: parameters and every leaf of the optimizer state, bit for bit
    (XLA:CPU adds a row's repeated gradients in the batch's order on both
    sides). ``kernel``: the rows reach the interaction's Mosaic kernel
    (interpreted) and their cotangent leaves its backward feature-major, on
    both sides; under a mesh of one device, because with eight and no mesh
    the model's fused entry is the einsum."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.parallel import make_mesh

    module, loss_fn, tx = _dlrm(kernel=kernel), _LOSSES["bce"], _optimizers()[name]()
    batches = list(_batches(6))
    params = module.init(jax.random.PRNGKey(0), batches[0][0])
    plan = row_update.plan(module, tx, params, batches[0][0], BATCH)
    assert plan.paths == ROW_PATHS and not plan.reason
    rows_state = 2 if name == "adagrad" else 1  # table + accumulator
    assert plan.bytes_skipped == rows_state * (5000 + 2000 - 2 * BATCH) * 8 * 4

    @jax.jit
    def dense(p, s, x, y):
        loss, g = jax.value_and_grad(lambda p: loss_fn(module.apply(p, x), y))(p)
        u, s = tx.update(g, s, p)
        return optax.apply_updates(p, u), s, loss

    step = jax.jit(make_train_step(module, loss_fn, tx, plan.paths))
    want = got = (params, tx.init(params))
    total = jnp.zeros(())
    with jax.set_mesh(make_mesh({"data": 1}, jax.devices()[:1])):
        assert ("pallas_call" in str(jax.make_jaxpr(step)(
            *got, total, *batches[0]))) == kernel
        for x, y in batches:
            *want, loss = dense(*want, x, y)
            *got, total = step(*got, total, x, y)
    assert jax.tree.structure(want) == jax.tree.structure(got)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the table moved, and not only where it was touched last
    assert not np.array_equal(
        np.asarray(params["params"]["embedding_0"]),
        np.asarray(got[0]["params"]["embedding_0"]))


def test_sorted_unique():
    import jax.numpy as jnp

    ids = jnp.asarray([[5, 1, 5, 9, 1, 1], [0, 0, 0, 0, 0, 0],
                       [3, 2, 1, 0, 4, 5]], jnp.int32)
    sizes = [10, 4, 6]
    uniq, inv = row_update.sorted_unique(ids, sizes)
    uniq = np.asarray(uniq)
    assert (np.diff(uniq, axis=1) > 0).all()  # ascending, no repeats
    for row, row_ids, size in zip(uniq, np.asarray(ids), sizes):
        # the distinct ids, then padding past the last row
        assert row[row < size].tolist() == sorted(set(row_ids.tolist()))
    np.testing.assert_array_equal(
        np.take_along_axis(uniq, np.asarray(inv), 1),
        np.asarray(ids))


@pytest.mark.parametrize(
    "name,engages",
    [("adagrad", True), ("sgd", True), ("adam", False), ("adamw", False),
     ("dlrm_optimizer", False), ("clip+adagrad", False),
     ("adagrad+apply_every", False), ("adagrad+schedule", False)],
)
def test_optimizer_probe_decides_the_fit(session, criteo_df, name, engages):
    """(2) the probe, through a fit: ``fit_stats_`` and the gauges say how
    many parameters took the row path, and why none did."""
    from raydp_tpu import obs

    est = _criteo_est(optimizer=_optimizers()[name], num_epochs=1)
    est.fit(dataframe_to_dataset(criteo_df))
    stats = est.fit_stats_["row_update"]
    # the probe has a span of its own and is not counted as compilation
    spans = [r for r in est.last_fit_records_
             if "row_update_probe" in r["name"] + str(r.get("args", {}).get("what"))]
    assert [r["name"] for r in spans] == ["estimator.row_update_probe"]
    assert abs(stats["probe_seconds"] - spans[0]["dur"] / 1e6) < 1e-5
    gauges = obs.metrics.snapshot()
    for key in ("params", "bytes_skipped"):
        assert gauges[f"estimator.row_update.{key}"]["value"] == stats[key]
    # what the model says of its interaction (``DLRM.fit_facts``) is on every
    # compile span of the fit, the FLOPs probe's among them (it still runs),
    # and its number a gauge
    compiles = [r["args"] for r in est.last_fit_records_
                if r["name"] == "estimator.compile"]
    assert compiles and all(
        a["interaction_operand"] == "feature_major"
        and a["interaction_kernel"] == "xla" for a in compiles)
    assert "flops_probe" in [a.get("what") for a in compiles]
    assert gauges["model.interaction.row_blocks"]["value"] == 3  # h, c0, c1
    if engages:
        assert stats["paths"] == ["params/embedding_0"] and not stats["reason"]
        assert stats["bytes_skipped"] > 0
        compiles = [r for r in est.last_fit_records_
                    if r["name"] == "estimator.compile"
                    and "row_update_params" in r.get("args", {})]
        assert compiles and all(
            r["args"]["row_update_params"] == 1
            and r["args"]["row_update_bytes_skipped"] == stats["bytes_skipped"]
            for r in compiles)
    else:
        assert stats["params"] == 0 and stats["bytes_skipped"] == 0
        assert "optimizer" in stats["reason"]
        if "+" in name and "clip" not in name:
            assert "beside the parameters'" in stats["reason"]


def test_shape_rule_and_undeclared_model_give_reasons():
    import jax
    import optax

    x, _ = next(_batches(1))
    module = _dlrm()
    params = module.init(jax.random.PRNGKey(0), x)
    few = row_update.plan(module, optax.adagrad(0.1), params, x, 4096)
    assert not few.paths and "rows to a row of the batch" in few.reason

    import flax.linen as nn

    mlp = nn.Dense(1)
    dense = x[0]
    none = row_update.plan(
        mlp, optax.adagrad(0.1), mlp.init(jax.random.PRNGKey(0), dense),
        dense, BATCH)
    assert not none.paths and "declares no" in none.reason


def _lowered(step, params, tx, x, y):
    import jax
    import jax.numpy as jnp

    text = jax.jit(step).lower(
        params, tx.init(params), jnp.zeros(()), x, y).as_text()
    return re.sub(r"@jit_\w+", "@jit_step", text)


@pytest.mark.parametrize("case", ["mlp", "dlrm+dlrm_optimizer"])
def test_bypass_traces_the_dense_step(case, monkeypatch):
    """(3) a module that declares nothing, and a DLRM under an optimizer
    the probe refuses, lower to the step the parent commit traced: the same
    text, so no ``sort`` and no ``scatter`` it did not have, on a TPU too
    (where the row path would bring the write-back kernel)."""
    import jax
    import optax

    monkeypatch.setattr(backend, "on_tpu", lambda: True)

    x, y = next(_batches(1))
    loss_fn = _LOSSES["bce"]
    if case == "mlp":
        import flax.linen as nn

        module, tx, x = nn.Dense(1), optax.adagrad(0.05), x[0]
    else:
        module, tx = _dlrm(), _optimizers()["dlrm_optimizer"]()
    params = module.init(jax.random.PRNGKey(0), x)
    plan = row_update.plan(module, tx, params, x, BATCH)
    assert not plan.paths and not plan.kernel_paths
    assert plan.stats()["write_back"] == {
        "kernel": 0, "scatter": 0, "reason": ""}

    def parent_step(params, opt_state, loss_sum, x, y):
        with jax.named_scope("loss_and_grad"):
            loss, grads = jax.value_and_grad(
                lambda p: loss_fn(module.apply(p, x), y))(params)
        with jax.named_scope("optimizer_update"):
            updates, opt_state = tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
        return params, opt_state, loss_sum + loss

    got = _lowered(
        make_train_step(module, loss_fn, tx, plan.paths, plan.kernel_paths),
        params, tx, x, y)
    assert got == _lowered(parent_step, params, tx, x, y)
    assert "stablehlo.sort" not in got and "row_write_back" not in got
    if case == "mlp":
        assert "stablehlo.scatter" not in got
    # and the row path is what brings them
    rows = _lowered(
        make_train_step(_dlrm(), loss_fn, optax.adagrad(0.05), ROW_PATHS),
        _dlrm().init(jax.random.PRNGKey(0), next(_batches(1))[0]),
        optax.adagrad(0.05), *next(_batches(1)))
    assert "stablehlo.sort" in rows and "stablehlo.scatter" in rows


def _criteo_est(**kw):
    from raydp_tpu.models import DLRM

    defaults = dict(
        # table 0 (c0 < 1000) stands above the shape rule at batch 32, table 1
        # (50 rows) below it
        model=DLRM(vocab_sizes=[1200, 50], num_dense=2, embed_dim=8,
                   use_pallas_interaction=False),
        optimizer="adagrad", loss="bce",
        feature_columns=["d0", "d1", "c0", "c1"],
        categorical_columns=["c0", "c1"], label_column="label",
        batch_size=BATCH, num_epochs=2, learning_rate=5e-2, seed=0,
    )
    defaults.update(kw)
    return JaxEstimator(**defaults)


def _losses(history):
    return [rec["train_loss"] for rec in history] + [
        rec["eval_loss"] for rec in history if "eval_loss" in rec]


@pytest.fixture
def dense_only(monkeypatch):
    """The dense step for the same fit, with no knob in the program: the
    shape rule's constant raised past every table."""

    def fit_dense(fit):
        with monkeypatch.context() as m:
            m.setattr(row_update, "MIN_ROWS_PER_BATCH_ROW", 10 ** 9)
            return fit()

    return fit_dense


@pytest.mark.parametrize("runner", ["resident", "streamed", "segments_ckpt"])
def test_fits_reach_the_dense_steps_losses(session, criteo_df, dense_only,
                                           runner):
    """(4) the two runners that wrap the step (the segment runner fed from
    a stream and from staged data over the limit), row path against dense
    step, and a resume from a checkpoint the row path wrote into the
    unchanged pytree."""
    ds = dataframe_to_dataset(criteo_df)
    kw = {
        "resident": dict(),
        "streamed": dict(streaming=True, shuffle=False),
        "segments_ckpt": dict(scan_memory_limit=1, save_every_steps=8),
    }[runner]

    def fit(**more):
        est = _criteo_est(**kw, **more)
        return est, _losses(est.fit(ds, ds))

    ckpt = tempfile.mkdtemp() if runner == "segments_ckpt" else None
    more = dict(checkpoint_dir=ckpt) if ckpt else {}
    est, rows = fit(**more)
    assert est.fit_stats_["row_update"]["params"] == 1
    est_dense, dense = dense_only(lambda: fit(
        **(dict(checkpoint_dir=tempfile.mkdtemp()) if ckpt else {})))
    assert est_dense.fit_stats_["row_update"]["params"] == 0
    # not bitwise: the batch is sharded over the 8-device data mesh, where
    # the two programs reduce across devices in different orders
    np.testing.assert_allclose(rows, dense, rtol=1e-5)
    if ckpt:
        # epoch 0's checkpoint restores into the same pytree under either
        # step, and epoch 1 replayed from it ends where the whole fit ended
        _, resumed = fit(checkpoint_dir=ckpt, resume_from_epoch=0)
        _, resumed_dense = dense_only(
            lambda: fit(checkpoint_dir=ckpt, resume_from_epoch=0))
        np.testing.assert_allclose(resumed, [rows[1], rows[3]], rtol=1e-5)
        np.testing.assert_allclose(resumed_dense, resumed, rtol=1e-5)


@pytest.mark.parametrize("name", ["adagrad", "sgd"])
def test_sharded_tables_parity(cpu_mesh_devices, name):
    """(5) tables over ``model``, batch over ``data`` on the 8-device
    XLA:CPU mesh under ``dlrm_sharding_rules()``: XLA partitions the gather
    and the scatter like the ``take`` they replace."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raydp_tpu.models import dlrm_sharding_rules
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 4, "model": 2}, cpu_mesh_devices[:8])
    module, loss_fn, tx = _dlrm(), _LOSSES["bce"], _optimizers()[name]()
    batches = list(_batches(4, seed=1))
    params = module.init(jax.random.PRNGKey(1), batches[0][0])
    sharded = jax.device_put(params, dlrm_sharding_rules()(mesh, params))
    assert sharded["params"]["embedding_0"].sharding.spec == P("model", None)
    put = lambda a: jax.device_put(a, NamedSharding(mesh, P("data")))  # noqa: E731

    def run(step, params):
        state = (params, tx.init(params), jnp.zeros(()))
        with jax.set_mesh(mesh):
            step = jax.jit(step)
            for x, y in batches:
                state = step(*state, jax.tree.map(put, x), put(y))
        return state

    # what a fit would run here: the scatter's step, its text unchanged
    plan = row_update.plan(module, tx, sharded, batches[0][0], BATCH)
    assert plan.paths == ROW_PATHS and not plan.kernel_paths
    got = run(make_train_step(module, loss_fn, tx, plan.paths,
                              plan.kernel_paths), sharded)
    want = run(make_train_step(module, loss_fn, tx), sharded)
    assert got[0]["params"]["embedding_0"].sharding.is_equivalent_to(
        sharded["params"]["embedding_0"].sharding, 2)
    for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7)
