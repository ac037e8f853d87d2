"""Row write-back kernel (ops/row_write_back.py) in interpret mode: what it
writes is what ``leaf.at[idx].set(rows, mode="drop")`` writes, bit for bit,
alone and through a fit."""

import numpy as np
import pytest

from raydp_tpu.estimator import row_update
from raydp_tpu.estimator.jax_estimator import _LOSSES, make_train_step
from raydp_tpu.ops import backend, row_gather, row_write_back as rwb
from tests.test_jax_estimator import criteo_df  # noqa: F401 - a fixture
from tests.test_row_update import (
    BATCH, ROW_PATHS, _batches, _criteo_est, _dlrm, _losses, _optimizers,
)

SLOTS = 2048


@pytest.fixture(scope="module")
def session():
    """An ETL session under this module's own name (tests/test_row_update.py
    says why)."""
    import raydp_tpu

    s = raydp_tpu.init_etl("test-row-write-back", num_executors=2,
                           executor_cores=1, executor_memory="300M")
    yield s
    raydp_tpu.stop_etl()


def _ids(case, size, rng):
    """The batch's ids of a case, before ``sorted_unique``."""
    return {
        # 1,200 distinct or so: the rest of the 2048 slots is padding
        "padding": lambda: rng.integers(0, size, SLOTS) // 2 * 2,
        "many_in_one_block": lambda: np.concatenate(
            [rng.integers(128, 256, 200), rng.integers(0, size, 56)]),
        "last_block_and_last_row": lambda: np.concatenate(
            [np.arange(size // 128 * 128, size),
             np.minimum([size - 1, 0, 127, 128], size - 1)]),
        "one_id": lambda: np.full(64, size - 1),
        "all_distinct": lambda: rng.permutation(size)[:SLOTS],
    }[case]()


@pytest.mark.parametrize("case, size, tables", [
    ("padding", 5000, 1),
    ("many_in_one_block", 5000, 1),
    ("last_block_and_last_row", 5000, 1),
    ("last_block_and_last_row", 100, 1),   # under one block
    ("last_block_and_last_row", 1024, 1),  # whole blocks only
    ("padding", 1024, 2),
    ("one_id", 5000, 1),
    ("one_id", 77, 2),
    ("all_distinct", 3000, 1),
    ("all_distinct", 200_000, 2),  # a parameter and its state, one call
    ("many_in_one_block", 641, 2),
])
def test_kernel_writes_what_the_scatter_writes(case, size, tables):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(size + tables)
    ids = _ids(case, size, rng)
    uniq, _ = row_update.sorted_unique(
        jnp.asarray(ids, jnp.int32)[None], [size])
    idx = uniq[0]
    distinct = int((np.asarray(idx) < size).sum())
    assert distinct == len(set(ids.tolist()))
    leaves = [jnp.asarray(rng.standard_normal((size, 16)), jnp.float32)
              for _ in range(tables)]
    rows = [jnp.asarray(rng.standard_normal((len(ids), 16)), jnp.float32)
            for _ in range(tables)]
    # a few bit patterns an arithmetic path would not carry over
    rows[0] = rows[0].at[0, :4].set(
        jnp.asarray([-0.0, np.inf, np.nan, 1e-42], jnp.float32))
    # few slots in flight, so that the ring comes round many times
    got = jax.jit(lambda t, r, i: rwb.row_write_back(
        t, r, i, interpret=True, ahead=4, ring=8))(leaves, rows, idx)
    for leaf, new, out in zip(leaves, rows, got):
        want = leaf.at[idx].set(new, mode="drop")
        assert out.shape == want.shape and out.dtype == want.dtype
        np.testing.assert_array_equal(
            np.asarray(out).view(np.uint32), np.asarray(want).view(np.uint32))
        assert not np.array_equal(np.asarray(out), np.asarray(leaf),
                                  equal_nan=True)


@pytest.mark.parametrize("shape, dtype, why", [
    ((5000, 16), "float32", ""), ((5000, 128), "float32", ""),
    ((5000, 8), "float32", ""),
    ((5000, 16), "bfloat16", "bfloat16"), ((5000, 12), "float32", "12"),
    ((5000, 256), "float32", "256"), ((5000, 4, 4), "float32", "3 axes"),
])
def test_kernel_says_what_it_supports(shape, dtype, why):
    got = rwb.supports(shape, dtype)
    assert (why in got) if why else not got


@pytest.fixture
def kernel_everywhere(monkeypatch):
    """The kernel's arm on the CPU backend (interpreted there, by
    ``ops/backend.py``'s rule): the one thing ``_scatter_reason`` reads that
    a test can set without a knob in the program."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    # the plan then reads the rows through the gather kernel too
    for module in (rwb, row_gather):
        monkeypatch.setattr(module, "pallas_interpret",
                            lambda interpret=None: True)


@pytest.mark.parametrize("name", ["adagrad", "sgd"])
def test_step_through_the_kernel_equals_the_scatter_step(kernel_everywhere,
                                                         name):
    """The row-path step with every leaf written by the kernel against the
    same step through XLA's scatter: parameters and optimizer state, bit for
    bit, over batches with repeated ids and ids at ``vocab - 1``."""
    import jax
    import jax.numpy as jnp

    module, loss_fn, tx = _dlrm(), _LOSSES["bce"], _optimizers()[name]()
    batches = list(_batches(4))
    params = module.init(jax.random.PRNGKey(0), batches[0][0])
    plan = row_update.plan(module, tx, params, batches[0][0], BATCH)
    leaves = 4 if name == "adagrad" else 2  # two tables (+ accumulators)
    assert plan.paths == plan.kernel_paths == ROW_PATHS
    assert plan.stats()["write_back"] == {
        "kernel": leaves, "scatter": 0, "reason": ""}
    raw = [make_train_step(module, loss_fn, tx, plan.paths, kernel)
           for kernel in (plan.kernel_paths, ())]
    steps = [jax.jit(step) for step in raw]
    args = (params, tx.init(params), jnp.zeros(()), *batches[0])
    assert "row_write_back" in str(jax.make_jaxpr(steps[0])(*args))
    # the FLOPs probe compiles the same step through the scatter: XLA counts
    # nothing inside a kernel, and the scatter's step stands for itself
    assert str(jax.make_jaxpr(raw[0].counted_as)(*args)) == str(
        jax.make_jaxpr(raw[1])(*args))
    assert not hasattr(raw[1], "counted_as")
    got = want = (params, tx.init(params), jnp.zeros(()))
    for x, y in batches:
        got, want = steps[0](*got, x, y), steps[1](*want, x, y)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fit_through_the_kernel_equals_the_scatter_fit(
        session, criteo_df, kernel_everywhere, monkeypatch):
    """The same fit twice, the kernel forced (interpreted) and XLA's scatter:
    losses and parameters equal after two epochs (the second epoch's updates
    read the Adagrad state the first wrote; the step test above compares the
    state itself); ``fit_stats_``, the gauge and the compile spans say which
    leaves went where."""
    import jax

    from raydp_tpu import obs
    from raydp_tpu.exchange import dataframe_to_dataset

    ds = dataframe_to_dataset(criteo_df)
    # one device: on the 8-device data mesh of the other fits the kernel
    # stays out, whatever the backend (the test below)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def fit():
        est = _criteo_est(mesh=mesh)
        losses = _losses(est.fit(ds, ds))
        return est, losses, jax.tree.map(np.asarray, est._params)

    est, losses, state = fit()
    stats = est.fit_stats_["row_update"]
    assert stats["write_back"] == {"kernel": 2, "scatter": 0, "reason": ""}
    assert obs.metrics.snapshot()[
        "estimator.row_update.dma_leaves"]["value"] == 2
    compiles = [r["args"] for r in est.last_fit_records_
                if r["name"] == "estimator.compile"
                and "row_update_params" in r.get("args", {})]
    assert compiles and all(a["row_update_dma_leaves"] == 2 for a in compiles)

    monkeypatch.undo()  # the CPU backend as it is
    est_scatter, losses_scatter, state_scatter = fit()
    back = est_scatter.fit_stats_["row_update"]["write_back"]
    assert back["kernel"] == 0 and back["scatter"] == 2
    assert "cpu" in back["reason"]
    assert obs.metrics.snapshot()[
        "estimator.row_update.dma_leaves"]["value"] == 0
    assert losses == losses_scatter
    assert (est.fit_stats_["flops_per_step"]
            == est_scatter.fit_stats_["flops_per_step"] > 0)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(state_scatter)):
        np.testing.assert_array_equal(a, b)


def test_leaves_on_a_mesh_stay_with_the_scatter(cpu_mesh_devices, monkeypatch):
    """A table laid out over several devices is the scatter's on any
    backend (a Pallas call is not partitioned), with the reason said; so is
    one the kernel's view does not cover."""
    import jax
    import optax

    from raydp_tpu.models import dlrm_sharding_rules
    from raydp_tpu.parallel import make_mesh

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    mesh = make_mesh({"data": 4, "model": 2}, cpu_mesh_devices[:8])
    module, tx = _dlrm(), optax.adagrad(0.05)
    x, _ = next(_batches(1))
    params = module.init(jax.random.PRNGKey(1), x)
    sharded = jax.device_put(params, dlrm_sharding_rules()(mesh, params))
    plan = row_update.plan(module, tx, sharded, x, BATCH)
    assert plan.paths == ROW_PATHS and not plan.kernel_paths
    back = plan.stats()["write_back"]
    assert back["kernel"] == 0 and back["scatter"] == 4
    assert "8 devices" in back["reason"] or "2 devices" in back["reason"]

    half = jax.tree.map(lambda p: p.astype("bfloat16"), params)
    plan = row_update.plan(module, tx, half, x, BATCH)
    if plan.paths:  # the probe may refuse bfloat16 for its own reasons
        assert not plan.kernel_paths
        assert "bfloat16" in plan.stats()["write_back"]["reason"]
