"""JaxEstimator tests — the reference's estimator test shape (test_torch.py:
29-88): tiny synthetic linear problem z = 3x + 4y + 5, few epochs, loss must
fall, parametrized object-store vs parquet staging path."""

import os
import tempfile

import numpy as np
import pandas as pd
import pytest

import raydp_tpu
from raydp_tpu.estimator import JaxEstimator
from raydp_tpu.exchange import dataframe_to_dataset


@pytest.fixture(scope="module")
def session():
    s = raydp_tpu.init_etl(
        "test-est", num_executors=2, executor_cores=1, executor_memory="300M"
    )
    yield s
    raydp_tpu.stop_etl()


@pytest.fixture(scope="module")
def linear_df(session):
    rng = np.random.default_rng(0)
    n = 2048
    x = rng.random(n).astype(np.float32)
    y = rng.random(n).astype(np.float32)
    pdf = pd.DataFrame({"x": x, "y": y, "z": 3 * x + 4 * y + 5})
    return session.from_pandas(pdf, num_partitions=4)


def _mlp():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(32)(x))
            x = nn.relu(nn.Dense(16)(x))
            return nn.Dense(1)(x)

    return MLP()


def _autoencoder():
    """A model whose loss is its own and reads no label column."""
    import flax.linen as nn
    import jax.numpy as jnp

    class AutoEncoder(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(x.shape[-1])(nn.tanh(nn.Dense(8)(x)))

        def loss(self, x, y=None):
            return jnp.mean((self(x) - x) ** 2)

    return AutoEncoder()


def _reference_fit(module, tx, loss, seed, epochs):
    """What the scan runners are held to: a plain loop that calls
    ``make_train_step``'s step once a batch. ``epochs``: an iterable per
    epoch of host ``(x, y)`` batches in the order the fit under test takes
    them. Returns (each epoch's mean loss, the final params)."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.estimator.jax_estimator import (
        MODEL_LOSS, _LOSSES, make_train_step)

    step = jax.jit(make_train_step(module, _LOSSES[loss], tx))
    params, opt_state, losses = None, None, []
    for batches in epochs:
        loss_sum, steps = jnp.zeros((), jnp.float32), 0
        for x, y in batches:
            if params is None:
                args = (x, None) if loss == MODEL_LOSS else (x,)
                params = module.init(
                    jax.random.PRNGKey(seed), *args,
                    **({"method": "loss"} if loss == MODEL_LOSS else {}))
                opt_state = tx.init(params)
            params, opt_state, loss_sum = step(params, opt_state, loss_sum, x, y)
            steps += 1
        losses.append(float(loss_sum) / steps)
    return losses, params


def _staged_epochs(est, x, y, num_epochs):
    """A staged fit's batches: ``epoch_order``'s rows, batch after batch."""
    b = est.batch_size
    for epoch in range(num_epochs):
        order = est.epoch_order(epoch, len(x))[: len(x) // b * b]
        yield [
            (x[idx], None if y is None else y[idx])
            for idx in order.reshape(-1, b)
        ]


def _streamed_epochs(est, ds, num_epochs):
    """A streamed fit's batches: the Dataset's own block stream, seeded the
    way the estimator seeds an epoch."""
    for epoch in range(num_epochs):
        yield ds.iter_batches(
            est.batch_size, est.feature_columns, est.label_column,
            shuffle=est.shuffle, seed=est.seed + epoch, drop_last=True,
            streaming=True,
        )


def _assert_same_params(a, b, atol):
    import jax

    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), atol=atol)


@pytest.mark.parametrize("use_fs_directory", [False, True])
def test_fit_on_etl_loss_decreases(session, linear_df, use_fs_directory):
    train_df, eval_df = linear_df.random_split([0.8, 0.2], seed=1)
    est = JaxEstimator(
        model=_mlp,  # creator-fn form
        optimizer="adam",
        loss="mse",
        metrics=["mse", "mae"],
        feature_columns=["x", "y"],
        label_column="z",
        batch_size=64,
        num_epochs=6,
        learning_rate=3e-3,
        seed=0,
    )
    kwargs = {}
    if use_fs_directory:
        kwargs["fs_directory"] = tempfile.mkdtemp()
    history = est.fit_on_etl(train_df, eval_df, **kwargs)
    assert len(history) == 6
    assert history[-1]["train_loss"] < history[0]["train_loss"] * 0.3
    assert "eval_mse" in history[-1] and "eval_mae" in history[-1]

    model = est.get_model()
    pred = np.asarray(model(np.array([[0.5, 0.5]], dtype=np.float32)))
    assert abs(pred[0, 0] - 8.5) < 1.5


def test_fit_on_dataset_directly(session, linear_df):
    ds = dataframe_to_dataset(linear_df)
    est = JaxEstimator(
        model=_mlp(),
        optimizer="sgd",
        learning_rate=0.05,
        loss="mse",
        feature_columns=["x", "y"],
        label_column="z",
        batch_size=128,
        num_epochs=4,
        seed=0,
    )
    history = est.fit(ds)
    assert history[-1]["train_loss"] < history[0]["train_loss"]


def test_checkpoint_save_and_load(session, linear_df):
    ckpt = tempfile.mkdtemp()
    est = JaxEstimator(
        model=_mlp(),
        feature_columns=["x", "y"],
        label_column="z",
        batch_size=128,
        num_epochs=2,
        checkpoint_dir=ckpt,
        seed=0,
    )
    ds = dataframe_to_dataset(linear_df)
    est.fit(ds)
    assert os.path.isdir(os.path.join(ckpt, "epoch_1"))

    est2 = JaxEstimator(
        model=_mlp(), feature_columns=["x", "y"], label_column="z",
        checkpoint_dir=ckpt,
    )
    restored = est2.load_checkpoint(1)
    trained = est.get_model().params
    import jax

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b)),
        trained,
        restored,
    )


def test_resume_from_checkpoint(session, linear_df):
    """Step-level resume: restart training from a checkpointed epoch."""
    import tempfile

    ckpt = tempfile.mkdtemp()
    ds = dataframe_to_dataset(linear_df)
    est = JaxEstimator(
        model=_mlp(), feature_columns=["x", "y"], label_column="z",
        batch_size=128, num_epochs=3, checkpoint_dir=ckpt, seed=0,
    )
    est.fit(ds)

    resumed = JaxEstimator(
        model=_mlp(), feature_columns=["x", "y"], label_column="z",
        batch_size=128, num_epochs=5, checkpoint_dir=ckpt, seed=0,
        resume_from_epoch=2,
    )
    history = resumed.fit(ds)
    assert [r["epoch"] for r in history] == [3, 4]
    assert os.path.isdir(os.path.join(ckpt, "epoch_4"))


def test_retry_resumes_from_latest_checkpoint(session, linear_df):
    """fit(max_retries=N) must not replay finished epochs: after a failure it
    resumes from the latest committed checkpoint (ADVICE round 1)."""
    ckpt = tempfile.mkdtemp()
    ds = dataframe_to_dataset(linear_df)
    est = JaxEstimator(
        model=_mlp(), feature_columns=["x", "y"], label_column="z",
        batch_size=128, num_epochs=5, checkpoint_dir=ckpt, seed=0,
    )

    real_fit_once = est._fit_once
    calls = {"n": 0}

    def flaky_fit_once(train_ds, evaluate_ds):
        calls["n"] += 1
        if calls["n"] == 1:
            # simulate a crash after epoch 2's checkpoint landed
            est.num_epochs = 3
            real_fit_once(train_ds, evaluate_ds)
            est.num_epochs = 5
            raise RuntimeError("injected crash after epoch 2")
        return real_fit_once(train_ds, evaluate_ds)

    est._fit_once = flaky_fit_once
    history = est.fit(ds, max_retries=1)
    # resumed at epoch 3 (latest checkpoint = epoch_2), not from scratch
    assert [r["epoch"] for r in history] == [3, 4]
    assert est._latest_checkpoint_epoch() == 4
    # retry state must not leak: a later fit() trains from scratch, and a
    # pre-existing checkpoint (epoch_4) must not short-circuit its retries
    assert est.resume_from_epoch is None
    history2 = est.fit(ds)
    assert [r["epoch"] for r in history2] == [0, 1, 2, 3, 4]


def test_dlrm_rejects_lossy_float_ids():
    """Float32 features cannot represent ids ≥ 2^24 exactly; DLRM must refuse
    at trace time instead of silently training on collided embedding rows."""
    import jax
    from raydp_tpu.models import DLRM

    model = DLRM(vocab_sizes=[2**24 + 2], num_dense=2, embed_dim=4)
    x = np.zeros((4, 3), dtype=np.float32)
    with pytest.raises(ValueError, match="exact-integer range"):
        jax.eval_shape(lambda a: model.init(jax.random.PRNGKey(0), a), x)

    # float64 carries ids up to 2^53 — accepted (needs x64 enabled, else
    # JAX silently downcasts the input to float32 and the guard fires).
    # jax.enable_x64 is the modern spelling; 0.4.x only has the
    # experimental entry point
    enable_x64 = getattr(jax, "enable_x64", None)
    if enable_x64 is None:
        from jax.experimental import enable_x64
    with enable_x64(True):
        ok = DLRM(vocab_sizes=[2**24 + 2], num_dense=2, embed_dim=4)
        x64 = np.zeros((4, 3), dtype=np.float64)
        jax.eval_shape(lambda a: ok.init(jax.random.PRNGKey(0), a), x64)


@pytest.fixture(scope="module")
def criteo_df(session):
    rng = np.random.default_rng(3)
    n = 768
    c0 = rng.integers(0, 1000, n)
    pdf = pd.DataFrame(
        {
            "d0": rng.random(n).astype(np.float32),
            "d1": rng.random(n).astype(np.float32),
            "c0": c0.astype(np.int64),
            "c1": rng.integers(0, 50, n).astype(np.int64),
            # learnable signal through the categorical: parity of c0
            "label": (c0 % 2).astype(np.float32),
        }
    )
    return session.from_pandas(pdf, num_partitions=4)


def _dlrm_est(vocabs, **kw):
    from raydp_tpu.models import DLRM

    defaults = dict(
        model=DLRM(vocab_sizes=list(vocabs), num_dense=2, embed_dim=8),
        optimizer="adam",
        loss="bce",
        feature_columns=["d0", "d1", "c0", "c1"],
        categorical_columns=["c0", "c1"],
        label_column="label",
        batch_size=64,
        num_epochs=4,
        learning_rate=2e-2,
        seed=0,
    )
    defaults.update(kw)
    return JaxEstimator(**defaults)


def test_dlrm_mixed_dtype_fit(session, criteo_df):
    """categorical_columns stages ids as a SEPARATE int32 array and DLRM
    consumes the (dense, ids) tuple — the whole-fit scan path must train
    through it (loss falls on a signal carried by a categorical)."""
    ds = dataframe_to_dataset(criteo_df)
    est = _dlrm_est([1000, 50])
    history = est.fit(ds)
    assert history[-1]["train_loss"] < history[0]["train_loss"] * 0.9
    # staged as (dense float32, ids int32) — ids never ride floats
    staged = next(iter(est._stage_cache.values()))
    assert isinstance(staged.features, tuple)
    assert staged.features[0].dtype == np.float32
    assert staged.features[0].shape[1] == 2
    assert staged.features[1].dtype == np.int32
    assert staged.features[1].shape[1] == 2
    # eval + get_model consume the tuple form too
    metrics = est.evaluate(ds)
    assert np.isfinite(metrics["eval_loss"])
    model = est.get_model()
    pred = model(
        (
            np.zeros((3, 2), dtype=np.float32),
            np.zeros((3, 2), dtype=np.int32),
        )
    )
    assert np.asarray(pred).shape == (3, 1)


def test_dlrm_mixed_dtype_fit_with_eval_and_ckpt(session, criteo_df):
    """The resident per-epoch scan: eval each epoch + checkpoint
    round-trip with tuple features."""
    ckpt = tempfile.mkdtemp()
    ds = dataframe_to_dataset(criteo_df)
    est = _dlrm_est([1000, 50], num_epochs=3, checkpoint_dir=ckpt)
    history = est.fit(ds, ds)
    assert len(history) == 3
    assert all(np.isfinite(r["eval_loss"]) for r in history)
    assert os.path.isdir(os.path.join(ckpt, "epoch_2"))
    assert est.fit_stats_["runner"] == "resident_scan"


def test_dlrm_mixed_dtype_streaming(session, criteo_df):
    """streaming=True with categorical_columns: tuple batches flow through
    the segment-scan producer (O(block) memory path)."""
    ds = dataframe_to_dataset(criteo_df)
    est = _dlrm_est([1000, 50], streaming=True, shuffle=False, num_epochs=3)
    history = est.fit(ds)
    assert len(history) == 3
    assert est.fit_stats_["runner"] == "segment_scan"
    assert history[-1]["train_loss"] < history[0]["train_loss"]


def test_streaming_takes_a_bool_only():
    """`streaming` is a bool since the hybrid mode went: a string that is
    merely truthy must not start to mean plain streaming in silence."""
    with pytest.raises(ValueError, match="streaming=True"):
        JaxEstimator(
            model=_mlp(), feature_columns=["x", "y"], label_column="z",
            streaming="hybrid",
        )


def test_dlrm_big_vocab_exact_ids(session):
    """A vocab BEYOND float32's 2^24 exact-integer range trains through the
    mixed-dtype path (the reference feeds int64 ids through torch at any
    vocab size; single-float32-matrix staging would collide adjacent ids).
    Distinct top-of-range ids must hit distinct embedding rows."""
    import jax
    from raydp_tpu.models import DLRM

    vocab = 2**24 + 8
    rng = np.random.default_rng(5)
    n = 256
    # ids at the top of the range, where float32 rounds to multiples of 2
    ids = (vocab - 8 + rng.integers(0, 8, n)).astype(np.int64)
    pdf = pd.DataFrame(
        {
            "d0": rng.random(n).astype(np.float32),
            "c0": ids,
            "label": (ids % 2).astype(np.float32),
        }
    )
    from raydp_tpu.models import dlrm_optimizer

    df = session.from_pandas(pdf, num_partitions=2)
    ds = dataframe_to_dataset(df)
    est = JaxEstimator(
        model=DLRM(vocab_sizes=[vocab], num_dense=1, embed_dim=2),
        # the Criteo-scale recipe: Adafactor on the tables (dense Adam's
        # two full-table moment copies OOM a real chip at big vocabs),
        # Adam on the MLPs
        optimizer=dlrm_optimizer(embedding_lr=0.5, dense_lr=1e-2),
        loss="bce",
        feature_columns=["d0", "c0"],
        categorical_columns=["c0"],
        label_column="label",
        batch_size=64,
        num_epochs=2,
        seed=0,
    )
    history = est.fit(ds)
    assert np.isfinite(history[-1]["train_loss"])
    # exactness: ids staged as int32 keep adjacent top-of-range values
    # distinct (float32 staging would collapse 2^24+1 → 2^24 etc.)
    staged = next(iter(est._stage_cache.values()))
    assert staged.features[1].dtype == np.int32
    assert set(np.unique(staged.features[1])) == set(np.unique(ids))
    # and the model separates two adjacent ids' embedding rows
    model = est.get_model()
    p0 = np.asarray(
        model((np.zeros((1, 1), np.float32), np.array([[vocab - 2]], np.int32)))
    )
    p1 = np.asarray(
        model((np.zeros((1, 1), np.float32), np.array([[vocab - 1]], np.int32)))
    )
    # parity signal learned: adjacent ids produce different predictions
    assert p0[0, 0] != p1[0, 0]


def test_categorical_columns_must_be_features():
    with pytest.raises(ValueError, match="not in feature_columns"):
        JaxEstimator(
            model=_mlp(),
            feature_columns=["a"],
            categorical_columns=["b"],
            label_column="z",
        )
    # a float categorical_dtype would reintroduce silent id collisions
    with pytest.raises(ValueError, match="integer dtype"):
        JaxEstimator(
            model=_mlp(),
            feature_columns=["a"],
            categorical_columns=["a"],
            categorical_dtype=np.float32,
            label_column="z",
        )


def test_all_categorical_features(session, criteo_df):
    """categorical_columns == feature_columns: the empty dense group is
    dropped and the model receives a 1-tuple (ids,)."""
    import flax.linen as nn
    import jax.numpy as jnp

    class EmbedOnly(nn.Module):
        @nn.compact
        def __call__(self, x):
            (ids,) = x
            table = self.param(
                "emb", nn.initializers.normal(0.1), (1000, 8), np.float32
            )
            rows = table[jnp.clip(ids[:, 0], 0, 999)]
            return nn.Dense(1)(rows)

    ds = dataframe_to_dataset(criteo_df)
    est = JaxEstimator(
        model=EmbedOnly(),
        loss="bce",
        feature_columns=["c0", "c1"],
        categorical_columns=["c0", "c1"],
        label_column="label",
        batch_size=64,
        num_epochs=2,
        seed=0,
    )
    history = est.fit(ds)
    assert np.isfinite(history[-1]["train_loss"])
    staged = next(iter(est._stage_cache.values()))
    assert isinstance(staged.features, tuple) and len(staged.features) == 1
    assert staged.features[0].dtype == np.int32


def test_null_categorical_fails_loudly(session):
    """A null in a categorical column must raise at staging, not silently
    gather embedding row 0 via NaN→INT_MIN→clamp."""
    pdf = pd.DataFrame(
        {
            "d0": np.ones(8, np.float32),
            "c0": pd.array([1, 2, None, 4, 5, 6, 7, 8], dtype="Int64"),
            "label": np.zeros(8, np.float32),
        }
    )
    df = session.from_pandas(pdf, num_partitions=1)
    ds = dataframe_to_dataset(df)
    from raydp_tpu.models import DLRM

    est = JaxEstimator(
        model=DLRM(vocab_sizes=[10], num_dense=1, embed_dim=2),
        loss="bce",
        feature_columns=["d0", "c0"],
        categorical_columns=["c0"],
        label_column="label",
        batch_size=4,
        num_epochs=1,
    )
    with pytest.raises(ValueError, match="contains nulls"):
        est.fit(ds)


def test_batch_sharded_over_mesh(session, linear_df, cpu_mesh_devices):
    """The train step must actually run sharded: batch size is rounded up to
    a multiple of the mesh and each device sees batch/8 rows."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    est = JaxEstimator(
        model=_mlp(),
        feature_columns=["x", "y"],
        label_column="z",
        batch_size=60,  # deliberately not divisible by 8 → rounds to 64
        num_epochs=1,
        mesh=mesh,
        seed=0,
    )
    ds = dataframe_to_dataset(linear_df)
    history = est.fit(ds)
    assert len(history) == 1


def test_streaming_fit(session, linear_df):
    """streaming=True trains block-by-block in O(block) host memory and still
    converges; eval runs through the same streamed path."""
    train_df, eval_df = linear_df.random_split([0.8, 0.2], seed=4)
    est = JaxEstimator(
        model=_mlp(),
        optimizer="adam",
        loss="mse",
        feature_columns=["x", "y"],
        label_column="z",
        batch_size=64,
        num_epochs=6,
        learning_rate=3e-3,
        seed=0,
        streaming=True,
    )
    history = est.fit_on_etl(train_df, eval_df)
    assert len(history) == 6
    assert history[-1]["train_loss"] < history[0]["train_loss"] * 0.3
    assert "eval_loss" in history[-1]
    model = est.get_model()
    pred = np.asarray(model(np.array([[0.5, 0.5]], dtype=np.float32)))
    assert abs(pred[0, 0] - 8.5) < 1.5


def test_stop_etl_after_conversion(session):
    """fit_on_etl(stop_etl_after_conversion=True) frees the ETL engine before
    training; data survives via ownership transfer (reference :352-361)."""
    rng = np.random.default_rng(2)
    n = 512
    x = rng.random(n).astype(np.float32)
    pdf = pd.DataFrame({"x": x, "y": x, "z": 7 * x + 1})
    df = raydp_tpu.etl.active_session().from_pandas(pdf, num_partitions=2)
    est = JaxEstimator(
        model=_mlp(),
        feature_columns=["x", "y"],
        label_column="z",
        batch_size=64,
        num_epochs=2,
        seed=0,
    )
    history = est.fit_on_etl(df, stop_etl_after_conversion=True)
    assert len(history) == 2
    # session is stopped now; the module fixture teardown tolerates this
    assert raydp_tpu.etl.active_session() is None or raydp_tpu.etl.active_session()._stopped


def _block_dataset(n=2048, seed=0):
    """Driver-written Dataset — independent of the (possibly stopped) ETL
    engine, so these tests can run after stop_etl_after_conversion ones."""
    import pyarrow as pa

    from raydp_tpu.etl.tasks import write_table_block
    from raydp_tpu.exchange.dataset import Dataset

    rng = np.random.default_rng(seed)
    x = rng.random(n).astype(np.float32)
    y = rng.random(n).astype(np.float32)
    table = pa.table({"x": x, "y": y, "z": 3 * x + 4 * y + 5})
    ref, cnt = write_table_block(table)
    return Dataset([ref], table.schema, [cnt])


def test_step_cadence_checkpoint_and_midepoch_resume(session):
    """save_every_steps writes epoch_N_step_K mid-epoch, and resuming from
    (epoch, step) replays EXACTLY the tail steps: the resumed run's final
    params match an uninterrupted run bit-for-bit (deterministic batch order
    per seed+epoch)."""
    import jax

    ckpt = tempfile.mkdtemp()
    ckpt_partial = tempfile.mkdtemp()
    ds = _block_dataset()
    # 2048 rows / batch 256 = 8 steps/epoch; checkpoints at steps 3 and 6
    common = dict(
        model=_mlp(), loss="mse", feature_columns=["x", "y"],
        label_column="z", batch_size=256, num_epochs=1,
        learning_rate=1e-2, seed=7, shuffle=True,
    )
    est_full = JaxEstimator(checkpoint_dir=ckpt, save_every_steps=3, **common)
    est_full.fit(ds)
    names = sorted(os.listdir(ckpt))
    # the completed epoch GC'd its step checkpoints; epoch_0 supersedes them
    assert names == ["epoch_0"], names

    # a CRASHED run leaves its mid-epoch step checkpoints behind
    est_partial = JaxEstimator(
        checkpoint_dir=ckpt_partial, save_every_steps=3, **common
    )
    orig = est_partial._save_checkpoint

    def crash_after_step3(params, epoch, opt_state, step=None):
        orig(params, epoch, opt_state, step=step)
        if step == 3:
            raise RuntimeError("injected crash after step-3 checkpoint")

    est_partial._save_checkpoint = crash_after_step3
    with pytest.raises(RuntimeError):
        est_partial.fit(ds)
    assert "epoch_0_step_3" in os.listdir(ckpt_partial)

    # resume from the step-3 checkpoint: replays steps 3..8 only and lands
    # on EXACTLY the uninterrupted run's params (same seed → same order)
    est_resumed = JaxEstimator(
        checkpoint_dir=ckpt_partial, resume_from_epoch=(0, 3), **common
    )
    est_resumed.fit(ds)
    full = jax.tree.leaves(est_full.get_model().params)
    resumed = jax.tree.leaves(est_resumed.get_model().params)
    for a, b in zip(full, resumed):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_retry_resumes_midepoch_from_step_checkpoint(session):
    """A crash between step checkpoints retries from the newest
    epoch_N_step_K — not from the last epoch boundary."""
    ckpt = tempfile.mkdtemp()
    ds = _block_dataset()
    est = JaxEstimator(
        model=_mlp(), loss="mse", feature_columns=["x", "y"],
        label_column="z", batch_size=256, num_epochs=1,
        learning_rate=1e-2, seed=7, checkpoint_dir=ckpt, save_every_steps=3,
    )
    calls = {"n": 0}
    orig = est._save_checkpoint

    def crash_after_step6(params, epoch, opt_state, step=None):
        orig(params, epoch, opt_state, step=step)
        if step == 6 and calls["n"] == 0:
            calls["n"] += 1
            raise RuntimeError("injected crash after step-6 checkpoint")

    est._save_checkpoint = crash_after_step6
    resumes = []
    real_fit_once = est._fit_once

    def spying_fit_once(train_ds, evaluate_ds):
        resumes.append(est.resume_from_epoch)
        return real_fit_once(train_ds, evaluate_ds)

    est._fit_once = spying_fit_once
    history = est.fit(ds, max_retries=2)
    assert resumes[0] is None
    assert resumes[1] == (0, 6), resumes  # resumed mid-epoch at step 6
    assert len(history) == 1 and history[0]["epoch"] == 0


def test_stream_segments_match_per_step(session):
    """Segment-scanned streaming (stream_scan_steps) trains identically to
    one call of the step a batch — with far fewer dispatches — including
    when step checkpoints snap the segment length to the save cadence."""
    import optax

    ds = _block_dataset(n=3000, seed=5)
    common = dict(
        model=_mlp(), loss="mse", feature_columns=["x", "y"],
        label_column="z", batch_size=64, num_epochs=2,
        learning_rate=1e-2, seed=1, streaming=True,
    )
    seg = JaxEstimator(stream_scan_steps=7, **common)
    seg.fit(ds)
    assert seg.fit_stats_["runner"] == "segment_scan"
    _, ref_params = _reference_fit(
        _mlp(), optax.adam(1e-2), "mse", 1, _streamed_epochs(seg, ds, 2))
    _assert_same_params(ref_params, seg.get_model().params, atol=1e-5)

    # step checkpoints along segment boundaries, resumable mid-epoch
    ckpt = tempfile.mkdtemp()
    partial_est = JaxEstimator(
        stream_scan_steps=16, save_every_steps=10, checkpoint_dir=ckpt,
        **common,
    )
    orig = partial_est._save_checkpoint

    def crash_at_20(params, epoch, opt_state, step=None):
        orig(params, epoch, opt_state, step=step)
        if epoch == 1 and step == 20:
            raise RuntimeError("boom")

    partial_est._save_checkpoint = crash_at_20
    with pytest.raises(RuntimeError):
        partial_est.fit(ds)
    assert "epoch_1_step_20" in os.listdir(ckpt)
    resumed = JaxEstimator(
        stream_scan_steps=16, checkpoint_dir=ckpt,
        resume_from_epoch=(1, 20), **common,
    )
    resumed.fit(ds)
    _assert_same_params(ref_params, resumed.get_model().params, atol=1e-5)


def _no_label_fit(how, x, **kw):
    """A ``label_column=None`` fit of 512 rows x 4 that cannot take the
    resident scan: staged over the limit, or streamed block by block.
    Returns (the estimator, the epochs of batches it takes)."""
    import pyarrow as pa

    est = JaxEstimator(
        model=_autoencoder(), loss="model", optimizer="adam",
        feature_columns=list("abcd"), label_column=None, batch_size=32,
        num_epochs=2, learning_rate=1e-2, seed=3,
        streaming=how == "streamed_no_label",
        **({} if how == "streamed_no_label" else {"scan_memory_limit": 1024}),
        **{"stream_scan_steps": 5, **kw},
    )
    if how == "streamed_no_label":
        from raydp_tpu.etl.tasks import write_table_block
        from raydp_tpu.exchange.dataset import Dataset

        blocks = [
            write_table_block(pa.table(dict(zip("abcd", part.T))))
            for part in np.split(x, 4)
        ]
        ds = Dataset(
            [ref for ref, _ in blocks],
            pa.schema([(c, pa.float32()) for c in "abcd"]),
            [cnt for _, cnt in blocks],
        )
        return est, ds, lambda: _streamed_epochs(est, ds, 2)
    return est, _ArraysDS(x, None), lambda: _staged_epochs(est, x, None, 2)


@pytest.mark.parametrize("save_every_steps", [None, 4], ids=["plain", "saving"])
@pytest.mark.parametrize("how", ["segments_no_label", "streamed_no_label"])
def test_no_label_fit_runs_segments(session, how, save_every_steps):
    """A fit with no label column that cannot take the resident scan runs
    5-step segments (4-step ones under ``save_every_steps=4``), and trains
    to what one call of the step a batch gives."""
    import optax

    x = np.random.default_rng(11).random((512, 4)).astype(np.float32)
    kw = {}
    if save_every_steps:
        kw = dict(save_every_steps=4, checkpoint_dir=tempfile.mkdtemp())
    est, ds, epochs = _no_label_fit(how, x, **kw)
    losses = [r["train_loss"] for r in est.fit(ds)]
    assert est.fit_stats_["runner"] == "segment_scan"
    # 16 steps an epoch: 5+5+5+1, or four of 4
    assert est.stream_stats_["segments"] == 2 * 4
    assert est.fit_stats_["flops_per_step"] > 0
    ref_losses, ref_params = _reference_fit(
        _autoencoder(), optax.adam(1e-2), "model", 3, epochs())
    np.testing.assert_allclose(losses, ref_losses, rtol=1e-4)
    _assert_same_params(ref_params, est.get_model().params, atol=1e-5)


@pytest.mark.parametrize("crash_at", [8, 6], ids=["segment_boundary", "mid_segment"])
def test_no_label_segment_fit_resumes_mid_epoch(crash_at):
    """``save_every_steps`` on a no-label segment fit, and a resume from the
    checkpoint it left mid-epoch: at a segment's boundary the producer
    skips whole segments, inside one it feeds batch by batch; both land on
    the uninterrupted reference's params."""
    import optax

    x = np.random.default_rng(12).random((512, 4)).astype(np.float32)
    ckpt = tempfile.mkdtemp()
    est, ds, epochs = _no_label_fit(
        "segments_no_label", x, save_every_steps=2, checkpoint_dir=ckpt)
    orig = est._save_checkpoint

    def crash(params, epoch, opt_state, step=None):
        orig(params, epoch, opt_state, step=step)
        if epoch == 1 and step == crash_at:
            raise RuntimeError("boom")

    est._save_checkpoint = crash
    with pytest.raises(RuntimeError):
        est.fit(ds)
    assert f"epoch_1_step_{crash_at}" in os.listdir(ckpt)
    # the resumed fit scans 4-step segments: step 8 is a boundary, 6 is not
    resumed, ds, _ = _no_label_fit(
        "segments_no_label", x, checkpoint_dir=ckpt,
        resume_from_epoch=(1, crash_at), stream_scan_steps=4,
    )
    resumed.fit(ds)
    assert resumed.fit_stats_["runner"] == "segment_scan"
    _, ref_params = _reference_fit(
        _autoencoder(), optax.adam(1e-2), "model", 3, epochs())
    _assert_same_params(ref_params, resumed.get_model().params, atol=1e-5)


@pytest.mark.parametrize("steps", [0, -3])
def test_a_segment_is_a_batch_or_more(steps):
    with pytest.raises(ValueError, match="stream_scan_steps"):
        JaxEstimator(model=_mlp(), stream_scan_steps=steps)


def test_keep_checkpoints_retention(session):
    ds = _block_dataset(n=1024, seed=9)
    ckpt = tempfile.mkdtemp()
    est = JaxEstimator(
        model=_mlp(), loss="mse", feature_columns=["x", "y"],
        label_column="z", batch_size=128, num_epochs=5,
        checkpoint_dir=ckpt, keep_checkpoints=2, seed=0,
    )
    est.fit(ds)
    names = sorted(os.listdir(ckpt))
    assert names == ["epoch_3", "epoch_4"], names


def test_fit_on_etl_accepts_pandas(session):
    """A plain pandas DataFrame is adopted via the running session
    (reference accepts pandas-on-Spark frames, spark/interfaces.py:27-39) —
    no manual from_pandas required."""
    from raydp_tpu.models import MLPRegressor

    # an earlier test in this module stops the fixture session via
    # stop_etl_after_conversion; make sure one is running
    if raydp_tpu.etl.active_session() is None:
        raydp_tpu.init_etl(
            "test-est-pandas", num_executors=2, executor_cores=1,
            executor_memory="300M",
        )
    rng = np.random.default_rng(5)
    n = 4096
    x = rng.random(n).astype(np.float32)
    y = rng.random(n).astype(np.float32)
    pdf = pd.DataFrame({"x": x, "y": y, "z": 3 * x + 4 * y + 5})

    est = JaxEstimator(
        model=MLPRegressor(),
        optimizer="adam",
        loss="mse",
        feature_columns=["x", "y"],
        label_column="z",
        batch_size=256,
        num_epochs=6,
        learning_rate=1e-2,
        seed=0,
    )
    history = est.fit_on_etl(pdf)  # pandas in, not an ETL DataFrame
    assert history[-1]["train_loss"] < history[0]["train_loss"] * 0.2


def test_fit_on_etl_rejects_junk_input(session):
    from raydp_tpu.models import MLPRegressor

    est = JaxEstimator(
        model=MLPRegressor(), feature_columns=["x"], label_column="y"
    )
    with pytest.raises(TypeError, match="DataFrame"):
        est.fit_on_etl([1, 2, 3])


class _ArraysDS:
    """A dataset that hands `_stage_host` its arrays: a staged fit with no
    cluster behind it."""

    def __init__(self, x, y):
        self.x, self.y = x, y

    def to_numpy(self, fc, lc, feature_dtype=None, label_dtype=None):
        return self.x.copy(), None if self.y is None else self.y.copy()


# every way a fit reaches each of its two training runners: constructor
# arguments, the training source (staged host arrays of 2048 rows x 3
# float32 + labels = 32 KiB, or a Dataset a streamed fit reads block by
# block) -> runner. An evaluation set or a checkpoint directory is no input
# of the choice: test_staged_fit_takes_the_resident_scan runs those fits.
_RUNNER_TABLE = [
    ("resident", dict(), "staged", "resident_scan"),
    ("resident-no-limit", dict(scan_memory_limit=None), "staged",
     "resident_scan"),
    ("resident-no-label", dict(label_column=None), "staged", "resident_scan"),
    ("staged-over-limit", dict(scan_memory_limit=1024), "staged",
     "segment_scan"),
    ("staged-under-a-batch", dict(batch_size=4096), "staged", "segment_scan"),
    ("streamed", dict(streaming=True), "dataset", "segment_scan"),
    ("streamed-no-label", dict(streaming=True, label_column=None), "dataset",
     "segment_scan"),
    ("no-label-over-limit", dict(scan_memory_limit=1024, label_column=None),
     "staged", "segment_scan"),
]


@pytest.mark.parametrize(
    "kwargs,source,expected",
    [row[1:] for row in _RUNNER_TABLE],
    ids=[row[0] for row in _RUNNER_TABLE],
)
def test_choose_runner_table(kwargs, source, expected):
    from raydp_tpu.estimator.jax_estimator import _HostArrays

    kwargs = {"label_column": "l", "batch_size": 128, **kwargs}
    est = JaxEstimator(
        model=_mlp(), feature_columns=["a", "b", "c"], **kwargs
    )
    if source == "staged":
        labels = (
            None if kwargs["label_column"] is None
            else np.zeros(2048, np.float32)
        )
        train_source = _HostArrays(np.zeros((2048, 3), np.float32), labels)
    else:
        train_source = object()  # a Dataset: anything but staged arrays
    assert est._choose_runner(train_source, kwargs["batch_size"]) == expected


@pytest.mark.parametrize("how", ["no-eval-no-ckpt", "evaluated", "checkpoint-dir"])
def test_staged_fit_takes_the_resident_scan(how):
    """A staged fit that nothing looks into between epochs (once one
    whole-fit dispatch), an evaluated one and a checkpointed one all run
    the per-epoch resident scan, and each epoch has its own record."""
    x = np.random.default_rng(2).random((512, 3)).astype(np.float32)
    ds = _ArraysDS(x, x.sum(1))
    est = JaxEstimator(
        model=_mlp(), loss="mse", feature_columns=["a", "b", "c"],
        label_column="l", batch_size=128, num_epochs=2,
        checkpoint_dir=tempfile.mkdtemp() if how == "checkpoint-dir" else None,
    )
    history = est.fit(ds, ds if how == "evaluated" else None)
    assert est.fit_stats_["runner"] == "resident_scan"
    assert [r["epoch"] for r in history] == [0, 1]
    assert all(r["epoch_seconds"] > 0 for r in history)
    assert ("eval_loss" in history[0]) == (how == "evaluated")


def test_resident_scan_matches_per_step_loop():
    """The resident per-epoch scan (with and without a checkpoint
    directory, under the default limit and under none) must train to what
    one call of the step a batch gives for the same seed: same host
    permutations, same step math — per-epoch losses equal to float32
    tolerance."""
    import optax

    from raydp_tpu.models import MLPRegressor

    rng = np.random.default_rng(9)
    n = 2048
    x = rng.random((n, 3)).astype(np.float32)
    y = (x @ np.array([1.0, -2.0, 0.5], np.float32)).astype(np.float32)

    def run(**kw):
        est = JaxEstimator(
            model=MLPRegressor(),
            optimizer="adam",
            loss="mse",
            feature_columns=["a", "b", "c"],
            label_column="l",
            batch_size=128,
            num_epochs=3,
            learning_rate=1e-2,
            shuffle=True,
            seed=4,
            **kw,
        )
        losses = [r["train_loss"] for r in est.fit(_ArraysDS(x, y))]
        assert est.fit_stats_["runner"] == "resident_scan"
        return est, losses

    est, scan = run()
    _, with_ckpt = run(checkpoint_dir=tempfile.mkdtemp())
    loop, _ = _reference_fit(
        MLPRegressor(), optax.adam(1e-2), "mse", 4,
        _staged_epochs(est, x, y, 3))
    np.testing.assert_allclose(scan, with_ckpt, rtol=1e-5)
    np.testing.assert_allclose(scan, loop, rtol=1e-4)


def test_no_limit_means_the_resident_scan_over_any_size():
    """``scan_memory_limit=None`` is no limit: the same 24 KiB that a
    limit of 1 KiB hands to the segment runner stay on the device, and the
    evaluation (one scan under no limit, a batch a call over the limit)
    reads the same loss."""
    x = np.random.default_rng(2).random((2048, 3)).astype(np.float32)
    ds = _ArraysDS(x, x.sum(1))

    def run(limit):
        est = JaxEstimator(
            model=_mlp(), loss="mse", feature_columns=["a", "b", "c"],
            label_column="l", batch_size=128, num_epochs=1,
            scan_memory_limit=limit,
        )
        history = est.fit(ds, ds)
        return est.fit_stats_["runner"], history[0]["eval_loss"]

    unlimited, limited = run(None), run(1024)
    assert (unlimited[0], limited[0]) == ("resident_scan", "segment_scan")
    np.testing.assert_allclose(unlimited[1], limited[1], rtol=1e-4)


@pytest.mark.parametrize("limit, runner, builder", [
    (None, "resident_scan", "_build_scan_runner"),
    (1024, "segment_scan", "_build_stream_runner"),
])
def test_fit_once_holds_either_runner_by_the_same_seam(limit, runner, builder):
    """Both builders take the same arguments and return a ``_Runner``; the
    epoch loop starts it once, calls ``run_epoch`` once an epoch with the
    same five arguments for the same five results, and closes it on the way
    out, also when an epoch raises (the segment runner's producer thread is
    joined then: none is left behind)."""
    import threading

    from raydp_tpu.estimator.jax_estimator import _Runner

    x = np.random.default_rng(6).random((1024, 3)).astype(np.float32)
    ds = _ArraysDS(x, x.sum(1))
    est = JaxEstimator(
        model=_mlp(), loss="mse", feature_columns=["a", "b", "c"],
        label_column="l", batch_size=128, num_epochs=3, seed=2,
        scan_memory_limit=limit,
    )
    calls, fail_at = [], [None]
    build = getattr(est, builder)

    def spying_build(*args):
        assert len(args) == 5  # train_source, batch_size, mesh, step, donate
        real = build(*args)
        assert isinstance(real, _Runner)

        def run_epoch(*args):
            assert len(args) == 5
            calls.append(("run_epoch", args[2], args[3], args[4]))
            if len(calls) - 1 == fail_at[0]:
                raise RuntimeError("boom")
            out = real.run_epoch(*args)
            assert len(out) == 5 and out[3] == 1024 // 128
            return out

        return _Runner(
            run_epoch,
            lambda *a: calls.append(("start",) + a) or real.start(*a),
            lambda: calls.append(("close",)) or real.close(),
        )

    setattr(est, builder, spying_build)
    threads_before = threading.active_count()
    est.fit(ds)
    assert est.fit_stats_["runner"] == runner
    # seeds seed + epoch, whole epochs, no save callback without a directory
    assert calls == [("start", 0, 0)] + [
        ("run_epoch", 2 + epoch, 0, None) for epoch in range(3)
    ] + [("close",)]
    del calls[:]
    fail_at[0] = 2  # the second epoch raises
    with pytest.raises(RuntimeError, match="boom"):
        est.fit(ds)
    assert [c[0] for c in calls] == ["start", "run_epoch", "run_epoch", "close"]
    assert threading.active_count() == threads_before
