"""Device-speed streaming ingest (PR 7): Partitioner placement parity,
mixed-dtype wire staging, N-way upload streams, executor-side decode.

The A/B rule throughout: every toggle's ON arm must produce byte-identical
training results to its OFF arm (shard-direct vs driver-staged, wire-quant
vs an equivalently-quantized fp32 feed). The suite runs with
RAYDP_TPU_SANITIZE=donation,lockdep,leaks armed, so every staging buffer
these paths touch is also donation-checked for free.
"""

import numpy as np
import pandas as pd
import pytest

import raydp_tpu
from raydp_tpu.estimator import JaxEstimator
from raydp_tpu.exchange import dataframe_to_dataset


@pytest.fixture(scope="module")
def session():
    s = raydp_tpu.init_etl(
        "test-ingest", num_executors=2, executor_cores=1,
        executor_memory="300M",
    )
    yield s
    raydp_tpu.stop_etl()


def _mlp():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
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


def _block_dataset(n=2048, seed=0, f=2):
    """Driver-written Dataset, independent of the ETL engine."""
    import pyarrow as pa

    from raydp_tpu.etl.tasks import write_table_block
    from raydp_tpu.exchange.dataset import Dataset

    rng = np.random.default_rng(seed)
    cols = {f"x{i}": rng.random(n).astype(np.float32) for i in range(f)}
    z = sum((i + 1) * c for i, c in enumerate(cols.values())) + 1.0
    cols["z"] = z.astype(np.float32)
    table = pa.table(cols)
    ref, cnt = write_table_block(table)
    return Dataset([ref], table.schema, [cnt]), [f"x{i}" for i in range(f)]


# ---------------------------------------------------------------------------
# Partitioner unit behavior
# ---------------------------------------------------------------------------


def test_partitioner_shard_direct_matches_driver_staged(cpu_mesh_devices):
    """shard_inputs/shard_stacked land byte-identical, identically-sharded
    arrays whichever arm assembles them (make_array_from_process_local_data
    vs driver-staged sharded device_put)."""
    import jax
    from raydp_tpu.parallel import DataParallelPartitioner, make_mesh

    mesh = make_mesh({"data": 8}, jax.devices()[:8])
    direct = DataParallelPartitioner(mesh, "data", shard_direct=True)
    staged = DataParallelPartitioner(mesh, "data", shard_direct=False)

    rng = np.random.default_rng(3)
    batch = (
        rng.random((64, 5)).astype(np.float32),
        rng.integers(0, 2**31 - 1, (64, 2)).astype(np.int32),
    )
    a = direct.shard_inputs(batch)
    b = staged.shard_inputs(batch)
    for da, db in zip(a, b):
        assert da.sharding == db.sharding
        np.testing.assert_array_equal(np.asarray(da), np.asarray(db))

    stacked = rng.random((4, 64, 3)).astype(np.float32)
    sa = direct.shard_stacked(stacked)
    sb = staged.shard_stacked(stacked)
    assert sa.sharding == sb.sharding
    # stacked spec: scan dim replicated, batch dim sharded
    assert sa.sharding.spec[0] is None and sa.sharding.spec[1] == "data"
    np.testing.assert_array_equal(np.asarray(sa), np.asarray(sb))


def test_partitioner_counters_track_arms(cpu_mesh_devices):
    import jax
    from raydp_tpu.obs import metrics
    from raydp_tpu.parallel import DataParallelPartitioner, make_mesh

    mesh = make_mesh({"data": 8}, jax.devices()[:8])
    x = np.ones((16, 2), np.float32)
    before_d = metrics.counter("partitioner.shard_direct_puts").value
    before_s = metrics.counter("partitioner.driver_staged_puts").value
    DataParallelPartitioner(mesh, "data", shard_direct=True).shard_inputs(x)
    DataParallelPartitioner(mesh, "data", shard_direct=False).shard_inputs(x)
    assert metrics.counter("partitioner.shard_direct_puts").value == before_d + 1
    assert metrics.counter("partitioner.driver_staged_puts").value == before_s + 1


def test_null_partitioner_passthrough():
    from raydp_tpu.parallel import NullPartitioner

    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    out = NullPartitioner().shard_inputs((x, None))
    np.testing.assert_array_equal(out[0], x)


# ---------------------------------------------------------------------------
# row-quantization helpers of exchange/jax_io.py
# ---------------------------------------------------------------------------


def test_quantize_widen_roundtrip_bit_identical():
    """The on-chip widen (jax) must match the host dequant reference
    bit-for-bit — both compute q·scale in float32."""
    from raydp_tpu.exchange.jax_io import (
        dequantize_rows,
        quantize_rows,
        widen_wire,
    )

    rng = np.random.default_rng(11)
    a = (rng.standard_normal((32, 64, 7)) * 100).astype(np.float32)
    a[3, 5] = 0.0  # an all-zero row must round-trip exactly
    q, scale = quantize_rows(a)
    assert q.dtype == np.int8 and scale.dtype == np.float32
    assert scale.shape == (32, 64, 1)
    host = dequantize_rows(q, scale)
    dev = np.asarray(widen_wire(__import__("jax").numpy.asarray(q),
                                __import__("jax").numpy.asarray(scale)))
    np.testing.assert_array_equal(host, dev)
    # all-zero row: scale forced to 1.0, values exactly zero
    np.testing.assert_array_equal(host[3, 5], np.zeros(7, np.float32))
    # int8 symmetric range respected and error bounded by scale/2 per value
    assert q.min() >= -127 and q.max() <= 127
    assert np.all(np.abs(host - a) <= scale / 2 + 1e-7)


# ---------------------------------------------------------------------------
# shard-direct A/B parity through a real streaming fit
# ---------------------------------------------------------------------------


def _stream_fit(ds, features, mesh=None, **kw):
    est = JaxEstimator(
        model=_mlp(), loss="mse", feature_columns=features,
        label_column="z", batch_size=64, num_epochs=2,
        learning_rate=1e-2, seed=3, shuffle=False, streaming=True,
        mesh=mesh, **kw,
    )
    est.fit(ds)
    return est


def test_streaming_shard_direct_ab_byte_identical(session, cpu_mesh_devices):
    """The tentpole parity rule: a streamed fit over an 8-device mesh lands
    bit-identical params whether segments arrive shard-direct
    (make_array_from_process_local_data) or driver-staged (device_put)."""
    import jax
    from jax.sharding import Mesh

    ds, features = _block_dataset(n=1536, seed=21)
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    direct = _stream_fit(ds, features, mesh=mesh, shard_direct=True)
    staged = _stream_fit(ds, features, mesh=mesh, shard_direct=False)
    assert direct.stream_stats_["shard_direct"] is True
    assert staged.stream_stats_["shard_direct"] is False
    for a, b in zip(
        __import__("jax").tree.leaves(direct.get_model().params),
        __import__("jax").tree.leaves(staged.get_model().params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_upload_streams_follow_prefetch_depth(session):
    """N-way ping-pong: the uploader rotates stream_prefetch_segments host
    staging buffers (min 2), and CPU jax auto-disables buffer reuse (the
    donation/zero-copy hazard class) — recorded in stream_stats_."""
    ds, features = _block_dataset(n=1024, seed=8)
    est = _stream_fit(ds, features, stream_prefetch_segments=4)
    assert est.stream_stats_["upload_streams"] == 4
    # CPU jax: device_put may zero-copy alias host numpy → reuse must be off
    assert est.stream_stats_["staging_buffer_reuse"] is False
    assert est.stream_stats_["segments"] > 0


# ---------------------------------------------------------------------------
# integer ids through a real streaming fit
# ---------------------------------------------------------------------------


def test_streaming_big_vocab_ids_exact(session):
    """Integer id leaves ride the streamed wire exact (int32): a DLRM
    streaming fit with vocab beyond float32's 2^24 exact range keeps
    adjacent top-of-range ids distinct through the segment runner."""
    from raydp_tpu.models import DLRM, dlrm_optimizer

    vocab = 2**24 + 8
    rng = np.random.default_rng(5)
    n = 512
    ids = (vocab - 8 + rng.integers(0, 8, n)).astype(np.int64)
    pdf = pd.DataFrame(
        {
            "d0": rng.random(n).astype(np.float32),
            "c0": ids,
            "label": (ids % 2).astype(np.float32),
        }
    )
    df = session.from_pandas(pdf, num_partitions=2)
    ds = dataframe_to_dataset(df)
    est = JaxEstimator(
        model=DLRM(vocab_sizes=[vocab], num_dense=1, embed_dim=2),
        optimizer=dlrm_optimizer(embedding_lr=0.5, dense_lr=1e-2),
        loss="bce",
        feature_columns=["d0", "c0"],
        categorical_columns=["c0"],
        label_column="label",
        batch_size=64,
        num_epochs=2,
        seed=0,
        streaming=True,
    )
    history = est.fit(ds)
    assert np.isfinite(history[-1]["train_loss"])
    assert est.stream_stats_["segments"] > 0
    # the parity signal is learnable only if adjacent ids hit DISTINCT
    # embedding rows — float32-collapsed ids could not separate these
    model = est.get_model()
    p0 = np.asarray(
        model((np.zeros((1, 1), np.float32), np.array([[vocab - 2]], np.int32)))
    )
    p1 = np.asarray(
        model((np.zeros((1, 1), np.float32), np.array([[vocab - 1]], np.int32)))
    )
    assert p0[0, 0] != p1[0, 0]


# ---------------------------------------------------------------------------
# executor-side decode
# ---------------------------------------------------------------------------


def test_streaming_executor_decode_active(session):
    """With a live ETL session the per-span Arrow→numpy decode runs in the
    executor pool (decode_segment), and the fit records it."""
    from raydp_tpu.obs import metrics

    rng = np.random.default_rng(2)
    n = 2048
    x = rng.random(n).astype(np.float32)
    y = rng.random(n).astype(np.float32)
    pdf = pd.DataFrame({"x": x, "y": y, "z": 3 * x + 4 * y + 5})
    df = session.from_pandas(pdf, num_partitions=4)
    before = metrics.counter("exchange.executor_decode_spans").value

    est = JaxEstimator(
        model=_mlp(), loss="mse", feature_columns=["x", "y"],
        label_column="z", batch_size=64, num_epochs=2,
        learning_rate=1e-2, seed=0, streaming=True,
    )
    history = est.fit_on_etl(df)
    assert history[-1]["train_loss"] < history[0]["train_loss"]
    assert est.stream_stats_["executor_decode"] is True
    assert metrics.counter("exchange.executor_decode_spans").value > before

    # toggle off: decode stays on the driver
    est_off = JaxEstimator(
        model=_mlp(), loss="mse", feature_columns=["x", "y"],
        label_column="z", batch_size=64, num_epochs=1,
        seed=0, streaming=True, stream_executor_decode=False,
    )
    est_off.fit_on_etl(df)
    assert est_off.stream_stats_["executor_decode"] is False


@pytest.mark.parametrize("label_column", ["z", None], ids=["labelled", "no_label"])
def test_streaming_executor_decode_matches_local(session, label_column):
    """Executor-side and driver-local decode must be byte-identical: same
    data, same seed, params bit-equal — with a label column and, for a
    model whose loss is its own, without one."""
    import jax

    rng = np.random.default_rng(23)
    n = 1024
    x = rng.random(n).astype(np.float32)
    y = rng.random(n).astype(np.float32)
    pdf = pd.DataFrame({"x": x, "y": y, "z": 3 * x + 4 * y + 5})
    df = session.from_pandas(pdf, num_partitions=4)

    def run(executor_decode):
        est = JaxEstimator(
            model=_mlp() if label_column else _autoencoder(),
            loss="mse" if label_column else "model",
            feature_columns=["x", "y"],
            label_column=label_column, batch_size=64, num_epochs=2,
            learning_rate=1e-2, seed=9, shuffle=False, streaming=True,
            stream_executor_decode=executor_decode,
        )
        est.fit_on_etl(df)
        assert est.fit_stats_["runner"] == "segment_scan"
        return est

    remote = run(True)
    local = run(False)
    assert remote.stream_stats_["executor_decode"] is True
    assert local.stream_stats_["executor_decode"] is False
    for a, b in zip(
        jax.tree.leaves(remote.get_model().params),
        jax.tree.leaves(local.get_model().params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_streaming_sessionless_falls_back_to_local_decode(session):
    """A Dataset with no session (driver-written blocks) streams fine —
    decode silently stays local."""
    ds, features = _block_dataset(n=512, seed=4)
    est = _stream_fit(ds, features)
    assert est.stream_stats_["executor_decode"] is False
