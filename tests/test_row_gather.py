"""Row gather kernel (ops/row_gather.py) in interpret mode: what it reads is
what ``leaf.at[idx].get(mode="clip")`` reads at every slot whose id is a
row, bit for bit, alone, through a step and through a fit; and where it
stays out (the CPU backend, a mesh, a table XLA's gather does not copy) the
step is the parent commit's, text for text."""

import hashlib

import numpy as np
import pytest

from raydp_tpu.estimator import row_update
from raydp_tpu.estimator.jax_estimator import _LOSSES, make_train_step
from raydp_tpu.ops import backend, row_gather as rg, row_write_back as rwb
from tests.test_jax_estimator import criteo_df  # noqa: F401 - a fixture
from tests.test_row_update import (
    BATCH, ROW_PATHS, _batches, _criteo_est, _dlrm, _losses, _lowered,
    _optimizers,
)
from tests.test_row_write_back import SLOTS, _ids


@pytest.fixture(scope="module")
def session():
    """An ETL session under this module's own name (tests/test_row_update.py
    says why)."""
    import raydp_tpu

    s = raydp_tpu.init_etl("test-row-gather", num_executors=2,
                           executor_cores=1, executor_memory="300M")
    yield s
    raydp_tpu.stop_etl()


@pytest.mark.parametrize("case, size, tables, width", [
    ("padding", 5000, 1, 16),
    ("many_in_one_block", 5000, 1, 16),
    ("last_block_and_last_row", 5000, 1, 16),
    ("last_block_and_last_row", 100, 1, 16),   # under one block
    ("last_block_and_last_row", 1024, 1, 16),  # whole blocks only
    ("padding", 1024, 2, 16),
    ("one_id", 5000, 1, 16),
    ("one_id", 77, 2, 8),
    ("all_distinct", 3000, 1, 16),
    ("all_distinct", 200_000, 2, 16),  # a parameter and its state, one call
    ("many_in_one_block", 641, 2, 128),
    ("padding", 5000, 1, 8),
    ("all_distinct", 2500, 1, 128),
])
def test_kernel_reads_what_the_gather_reads(case, size, tables, width):
    import jax
    import jax.numpy as jnp

    rng = np.random.default_rng(size + tables + width)
    ids = _ids(case, size, rng)
    uniq, _ = row_update.sorted_unique(
        jnp.asarray(ids, jnp.int32)[None], [size])
    idx = uniq[0]
    live = np.asarray(idx) < size
    assert live.sum() == len(set(ids.tolist()))
    if case == "all_distinct":
        assert live.sum() == SLOTS
    leaves = [jnp.asarray(rng.standard_normal((size, width)), jnp.float32)
              for _ in range(tables)]
    # a few bit patterns an arithmetic path would not carry over, in a row
    # that is read (the first id's)
    leaves[0] = leaves[0].at[int(idx[0]), :4].set(
        jnp.asarray([-0.0, np.inf, np.nan, 1e-42], jnp.float32))
    # few slots in flight, so that the ring comes round many times
    got = jax.jit(lambda t, i: rg.row_gather(
        t, i, interpret=True, chunk=4, ahead=4, ring=8))(leaves, idx)
    assert len(got) == tables
    for leaf, out in zip(leaves, got):
        want = leaf.at[idx].get(mode="clip")
        assert out.shape == want.shape and out.dtype == want.dtype
        np.testing.assert_array_equal(
            np.asarray(out)[live].view(np.uint32),
            np.asarray(want)[live].view(np.uint32))
        # a padding slot holds a defined constant, not what VMEM held
        assert not np.asarray(out)[~live].view(np.uint32).any()
    assert np.isnan(np.asarray(got[0])[0, 2])


def test_kernel_supports_what_the_write_back_supports():
    import jax.numpy as jnp

    assert rg.supports is rwb.supports
    idx = jnp.arange(8, dtype=jnp.int32)
    for shape, dtype, why in [((64, 16), jnp.bfloat16, "bfloat16"),
                              ((64, 12), jnp.float32, "12")]:
        with pytest.raises(ValueError, match=why):
            rg.row_gather([jnp.zeros(shape, dtype)], idx, interpret=True)
    with pytest.raises(ValueError, match="one shape"):
        rg.row_gather([jnp.zeros((64, 16)), jnp.zeros((32, 16))], idx,
                      interpret=True)
    with pytest.raises(ValueError, match="power of two"):
        rg.row_gather([jnp.zeros((64, 16))], idx, interpret=True, ring=12)


@pytest.fixture
def kernels_everywhere(monkeypatch):
    """Both kernels' arms on the CPU backend (interpreted there): the one
    thing the plan reads that a test can set without a knob in the program."""
    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    for module in (rwb, rg):
        monkeypatch.setattr(module, "pallas_interpret",
                            lambda interpret=None: True)


@pytest.mark.parametrize("name", ["adagrad", "sgd"])
def test_step_through_the_gather_kernel_equals_xlas_gather(
        kernels_everywhere, name):
    """The row-path step with every row-path leaf read by the kernel against
    the same step through XLA's gather (the write-back the kernel's, then
    XLA's scatter too): parameters, optimizer state and loss, bit for bit,
    over batches with repeated ids and ids at ``vocab - 1``."""
    import jax
    import jax.numpy as jnp

    module, loss_fn, tx = _dlrm(), _LOSSES["bce"], _optimizers()[name]()
    batches = list(_batches(4))
    params = module.init(jax.random.PRNGKey(0), batches[0][0])
    plan = row_update.plan(module, tx, params, batches[0][0], BATCH)
    leaves = 4 if name == "adagrad" else 2  # two tables (+ accumulators)
    assert plan.paths == plan.kernel_paths == plan.gather_paths == ROW_PATHS
    assert plan.stats()["gather"] == {"kernel": leaves, "xla": 0, "reason": ""}
    raw = [make_train_step(module, loss_fn, tx, plan.paths, *kernels)
           for kernels in ((plan.kernel_paths, plan.gather_paths),
                           (plan.kernel_paths,), ())]
    args = (params, tx.init(params), jnp.zeros(()), *batches[0])
    steps = [jax.jit(step) for step in raw]
    texts = [str(jax.make_jaxpr(step)(*args)) for step in raw[::2]]
    # one call a table: the parameter and its state together
    assert texts[0].count("name=row_gather") == 2
    assert "row_gather" not in texts[1]
    # the FLOPs probe compiles the step through XLA's gather and scatter
    assert str(jax.make_jaxpr(raw[0].counted_as)(*args)) == texts[1]
    traced = str(jax.make_jaxpr(raw[1])(*args))
    assert "name=row_write_back" in traced and "row_gather" not in traced
    states = [args[:3]] * 3
    for x, y in batches:
        states = [step(*state, x, y) for step, state in zip(steps, states)]
        for other in states[1:]:  # the loss of every step too
            for a, b in zip(jax.tree.leaves(states[0]),
                            jax.tree.leaves(other)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fit_through_the_gather_kernel_equals_xlas_gather_fit(
        session, criteo_df, kernels_everywhere, monkeypatch):
    """The same fit three times: both kernels (interpreted), XLA's gather
    with the kernel's write-back because the table is larger than any XLA
    copies, and the CPU backend as it is. Losses and parameters equal;
    ``fit_stats_``, the gauge and the compile spans say which leaves were
    read how, and why."""
    import jax

    from raydp_tpu import obs
    from raydp_tpu.exchange import dataframe_to_dataset

    ds = dataframe_to_dataset(criteo_df)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))

    def fit():
        est = _criteo_est(mesh=mesh)
        losses = _losses(est.fit(ds, ds))
        gauge = obs.metrics.snapshot()[
            "estimator.row_update.gather_leaves"]["value"]
        spans = [r["args"]["row_update_gather_leaves"]
                 for r in est.last_fit_records_
                 if r["name"] == "estimator.compile"
                 and "row_update_params" in r.get("args", {})]
        assert spans and set(spans) == {gauge}
        return (est.fit_stats_["row_update"], gauge, losses,
                jax.tree.map(np.asarray, est._params))

    stats, gauge, losses, params = fit()
    assert stats["gather"] == {"kernel": 2, "xla": 0, "reason": ""}
    assert stats["write_back"]["kernel"] == 2 and gauge == 2

    with monkeypatch.context() as m:  # no table is one XLA's gather copies
        m.setattr(row_update, "GATHER_KERNEL_MAX_ROWS", 1000)
        by_shape, gauge, losses_xla, params_xla = fit()
    assert by_shape["gather"]["kernel"] == 0 and by_shape["gather"]["xla"] == 2
    assert "1200 rows" in by_shape["gather"]["reason"]
    assert by_shape["write_back"]["kernel"] == 2 and gauge == 0

    monkeypatch.undo()  # the CPU backend as it is
    on_cpu, gauge, losses_cpu, params_cpu = fit()
    assert on_cpu["gather"]["kernel"] == 0 and on_cpu["gather"]["xla"] == 2
    assert "cpu" in on_cpu["gather"]["reason"] and gauge == 0

    assert losses == losses_xla == losses_cpu
    for other in (params_xla, params_cpu):
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(other)):
            np.testing.assert_array_equal(a, b)


# sha256 of the row-path step's lowered text (``_lowered``: tiny DLRM,
# Adagrad, batch 32) where the gather kernel does not engage: the CPU
# backend's, and the 8-device mesh's under ``dlrm_sharding_rules()``. Taken
# at 315a501, before the kernel existed, and again at PR 47, whose model
# stacks a sample's rows feature-major (``[D, B]`` slabs): the row path's
# own part of the text is as it was
PARENT_STEP = {
    "cpu": "07ca55fb2f772c2b457d3f72733e7633772b3ff422abf5f353b4120dc79f2fe8",
    "mesh": "ce23cc2aa7b752c435b0c4d096366558472b3108d613dee8fc77aa1ad807cfa6",
}


@pytest.mark.parametrize("where", ["cpu", "mesh"])
def test_cpu_and_mesh_keep_xlas_gather(where, cpu_mesh_devices, monkeypatch):
    """Off the TPU, and on a mesh on any backend, XLA's gather reads the
    rows, with the reason said, and the step lowers to the parent commit's
    text: the state's rows are taken where they were, nothing is added."""
    import jax
    import optax

    from raydp_tpu.models import dlrm_sharding_rules
    from raydp_tpu.parallel import make_mesh

    module, tx = _dlrm(), optax.adagrad(0.05)
    x, y = next(_batches(1))
    params = module.init(jax.random.PRNGKey(1), x)
    if where == "mesh":
        monkeypatch.setattr(backend, "on_tpu", lambda: True)
        mesh = make_mesh({"data": 4, "model": 2}, cpu_mesh_devices[:8])
        params = jax.device_put(params, dlrm_sharding_rules()(mesh, params))
    plan = row_update.plan(module, tx, params, x, BATCH)
    assert plan.paths == ROW_PATHS and not plan.gather_paths
    read = plan.stats()["gather"]
    assert read["kernel"] == 0 and read["xla"] == 4
    assert ("devices" if where == "mesh" else "cpu") in read["reason"]
    text = _lowered(
        make_train_step(module, _LOSSES["bce"], tx, plan.paths,
                        plan.kernel_paths, plan.gather_paths),
        params, tx, x, y)
    assert "row_gather" not in text and "stablehlo.gather" in text
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_STEP[where]


def test_the_shape_decides_between_the_kernel_and_xlas_gather(monkeypatch):
    """Of the leaves the write-back kernel takes, the gather kernel reads
    those of tables XLA's gather would copy whole: by rows, one constant. A
    leaf the write-back refuses is read by XLA's gather for the same reason."""
    import jax
    import optax

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    limit = row_update.GATHER_KERNEL_MAX_ROWS
    module = _dlrm(vocabs=(limit, 7, 300, limit + 1, 3))
    x, _ = next(_batches(1))
    params = jax.eval_shape(module.init, jax.random.PRNGKey(0), x)
    # the probe observes a toy tree: no table of this size is ever built
    plan = row_update.plan(module, optax.adagrad(0.05), params, x, BATCH)
    assert plan.paths == plan.kernel_paths == ROW_PATHS
    assert plan.gather_paths == ROW_PATHS[:1]
    read = plan.stats()["gather"]
    assert read["kernel"] == 2 and read["xla"] == 2
    assert f"{limit + 1} rows" in read["reason"]

    half = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, "bfloat16"), params)
    plan = row_update.plan(module, optax.adagrad(0.05), half, x, BATCH)
    if plan.paths:  # the probe may refuse bfloat16 for its own reasons
        read = plan.stats()["gather"]
        assert not plan.gather_paths and read["kernel"] == 0
        assert read["xla"] == 4 and "bfloat16" in read["reason"]
