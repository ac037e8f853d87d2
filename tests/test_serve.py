"""Online serving plane tests (raydp_tpu/serve/, docs/serving.md).

Covers the tentpole contracts end to end on a real multi-process cluster:

- the e2e demo path: ``fit_on_etl`` → checkpoint → ``serve.deploy`` →
  concurrent clients get predictions in parity with a direct
  ``estimator.evaluate``/``predict`` over the same rows;
- dynamic batching: deadline-trigger vs size-trigger, bucket padding
  correctness (padded rows never leak into responses), conf-off
  (``serve.dynamic_batching=false``) A/B parity;
- zero-drop failover: a replica SIGKILLed mid-request-stream drops zero
  requests, responses stay byte-identical to an unkilled run (single
  fixed bucket → deterministic shapes → bit-stable numerics), and the
  controller heals the pool;
- rolling reload: old weights serve until the new generation is warm —
  every in-flight response is exactly old-or-new, never torn;
- scale-out/scale-in counters + graceful drain semantics;
- the doorbell-path request round trip (pooled dispatch sockets observed);
- the estimator inference-loading satellites (``load_latest_checkpoint``
  restores params without building optimizer state; ``predict`` parity).

Numerics note (docs/serving.md): XLA lowers per batch shape, so per-row
results are bit-stable at a FIXED shape but not across shapes. Exact
equality assertions therefore always compare at the bucket shape the
serving path used.
"""

import os
import tempfile
import threading
import time

import numpy as np
import pandas as pd
import pytest

import raydp_tpu
from raydp_tpu import obs, serve
from raydp_tpu.estimator import JaxEstimator
from raydp_tpu.models import MLPRegressor

FEATURES = ["a", "b"]
HIDDEN = (8,)


def _make_estimator(ckpt_dir, seed=0, epochs=2):
    return JaxEstimator(
        model=MLPRegressor(hidden=HIDDEN),
        optimizer="adam",
        loss="mse",
        feature_columns=FEATURES,
        label_column="y",
        batch_size=64,
        num_epochs=epochs,
        learning_rate=1e-3,
        shuffle=True,
        seed=seed,
        checkpoint_dir=ckpt_dir,
        donate_state=False,
    )


@pytest.fixture(scope="module")
def served_model():
    """ONE fit for the whole module: fit_on_etl writes the checkpoint, the
    eval Dataset survives the session (ownership transfer), and every test
    deploys against the same weights. Returns (est, ckpt_dir, x, eval_ds)."""
    ckpt_dir = tempfile.mkdtemp(prefix="serve-ckpt-")
    rng = np.random.default_rng(0)
    n = 1024
    pdf = pd.DataFrame(
        {
            "a": rng.random(n).astype(np.float32),
            "b": rng.random(n).astype(np.float32),
        }
    )
    pdf["y"] = 2 * pdf["a"] + 3 * pdf["b"]
    est = _make_estimator(ckpt_dir)
    session = raydp_tpu.init_etl(
        "test-serve", num_executors=2, executor_cores=1,
        executor_memory="300M",
    )
    df = session.from_pandas(pdf, num_partitions=2)
    eval_ds = raydp_tpu.dataframe_to_dataset(df, _use_owner=True)
    # the acceptance demo's first two stages: fit_on_etl → checkpoint
    est.fit_on_etl(df)
    raydp_tpu.stop_etl(cleanup_data=False, del_obj_holder=False)
    x = pdf[FEATURES].to_numpy(np.float32)
    yield est, ckpt_dir, x, eval_ds
    try:
        from raydp_tpu.store import object_store as store

        store.delete(eval_ds.blocks)
    except Exception:
        pass


def _deploy(est, x, replicas=1, conf=None, **kwargs):
    base = {"serve.max_batch_size": 16}
    base.update(conf or {})
    return serve.deploy(
        est, replicas=replicas, conf=base, example=x[0], **kwargs
    )


def _bucket_reference(est, x_rows, bucket):
    """Ground truth at the bucket shape the serving path computes under:
    pad to ``bucket`` rows (repeat-last, the serving padding rule), apply
    with the same jit path, slice the valid rows. Per-row results at a
    fixed shape are composition-independent, so this matches any serving
    batch that landed in the same bucket bit-for-bit."""
    n = len(x_rows)
    padded = np.concatenate(
        [x_rows, np.repeat(x_rows[-1:], bucket - n, axis=0)]
    )
    return est.predict(padded)[:n]


# ---------------------------------------------------------------------------
# estimator satellites: inference loading + predict
# ---------------------------------------------------------------------------


def test_load_latest_checkpoint_parity_with_evaluate(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    fresh = _make_estimator(ckpt_dir)
    epoch, step = fresh.load_latest_checkpoint()
    assert epoch >= 0 and step is None  # epoch-complete wins over steps
    # params restored bit-identically, without any optimizer state built
    import jax

    trained = jax.tree_util.tree_leaves(est._params)
    loaded = jax.tree_util.tree_leaves(fresh._params)
    assert all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(trained, loaded)
    )
    # predict parity (same jit path, same shape → bit-identical)
    assert np.array_equal(est.predict(x), fresh.predict(x))
    # full parity with a post-fit in-memory evaluate on the same rows
    post_fit = est.evaluate(eval_ds)
    from_ckpt = fresh.evaluate(eval_ds)
    assert from_ckpt["eval_loss"] == pytest.approx(
        post_fit["eval_loss"], rel=1e-6
    )


def test_predict_requires_params():
    est = _make_estimator(None)
    with pytest.raises(RuntimeError, match="load_latest_checkpoint"):
        est.predict(np.zeros((1, 2), np.float32))


def test_load_latest_checkpoint_missing_dir():
    est = _make_estimator(tempfile.mkdtemp(prefix="empty-ckpt-"))
    with pytest.raises(FileNotFoundError):
        est.load_latest_checkpoint()


# ---------------------------------------------------------------------------
# e2e demo: deploy → concurrent clients → parity
# ---------------------------------------------------------------------------


def test_e2e_deploy_concurrent_clients_parity(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    with _deploy(est, x, replicas=2) as dep:
        results = {}
        errors = []

        def client(i):
            try:
                results[i] = dep.predict(x[i * 8 : i * 8 + 5])
            except Exception as exc:  # noqa: BLE001
                errors.append(repr(exc))

        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errors
        # a caller can see where each replica serves from
        import jax

        for info in dep.infos():
            assert info["platform"] == jax.devices()[0].platform
            assert info["device_kind"] == jax.devices()[0].device_kind
            assert info["device_count"] == len(jax.devices())
        # correctness: every client's rows match the direct model within
        # float tolerance regardless of which bucket its batch landed in
        for i, out in results.items():
            direct = est.predict(x[i * 8 : i * 8 + 5])
            assert out.shape == direct.shape
            np.testing.assert_allclose(out, direct, rtol=1e-5, atol=1e-6)
        # parity with evaluate: the served predictions reproduce eval_loss
        served = np.concatenate(
            [dep.predict(x[lo : lo + 16]) for lo in range(0, 1024, 16)]
        )
        y = 2 * x[:, 0] + 3 * x[:, 1]
        served_mse = float(np.mean((served.reshape(-1) - y) ** 2))
        assert served_mse == pytest.approx(
            est.evaluate(eval_ds)["eval_loss"], rel=1e-4
        )


# ---------------------------------------------------------------------------
# batching policy
# ---------------------------------------------------------------------------


def test_size_trigger_coalesces_full_batch(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    conf = {"serve.max_batch_size": 8, "serve.batch_deadline_ms": 2000}
    with _deploy(est, x, conf=conf) as dep:
        before = obs.metrics.counter("serve.batches").value
        t0 = time.monotonic()
        reqs = [dep.submit(x[i : i + 1]) for i in range(8)]
        outs = [r.result(30) for r in reqs]
        elapsed = time.monotonic() - t0
        # 8 queued rows == max_batch: the SIZE trigger fired — nowhere near
        # the 2s deadline
        assert elapsed < 1.0
        assert obs.metrics.counter("serve.batches").value - before == 1
        ref = _bucket_reference(est, x[:8], 8)
        for i, out in enumerate(outs):
            assert np.array_equal(out, ref[i : i + 1])


def test_deadline_trigger_flushes_partial_batch(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    conf = {"serve.max_batch_size": 64, "serve.batch_deadline_ms": 150}
    with _deploy(est, x, conf=conf) as dep:
        t0 = time.monotonic()
        req = dep.submit(x[:3])  # 3 rows << 64: only the deadline can fire
        out = req.result(30)
        elapsed = time.monotonic() - t0
        assert 0.1 <= elapsed < 5.0  # waited for the deadline, not forever
        assert np.array_equal(out, _bucket_reference(est, x[:3], 4))


def test_bucket_padding_never_leaks(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    conf = {"serve.max_batch_size": 16, "serve.batch_buckets": [16]}
    with _deploy(est, x, conf=conf) as dep:
        before = obs.metrics.counter("serve.padded_rows").value
        out = dep.predict(x[:5])
        # exactly the 5 valid rows come back — the 11 padded rows are
        # sliced off replica-side and never reach any response
        assert out.shape == (5, 1)
        assert obs.metrics.counter("serve.padded_rows").value - before == 11
        assert np.array_equal(out, _bucket_reference(est, x[:5], 16))


def test_conf_off_dynamic_batching_ab_parity(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    rows = [x[i : i + 1] for i in range(6)]
    with _deploy(est, x, conf={"serve.dynamic_batching": "false"}) as dep:
        off_arm = [dep.predict(r) for r in rows]
        # off = one dispatch per request, unpadded
        assert dep.batcher.stats()["queued_rows"] == 0
    with _deploy(est, x) as dep:
        # sequential single-row requests batch to bucket 1 — the same (1, F)
        # dispatch shape as the conf-off arm, so parity is BYTE-identical
        on_arm = [dep.predict(r) for r in rows]
    for a, b in zip(off_arm, on_arm):
        assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# lifecycle: rolling reload, scaling, drain
# ---------------------------------------------------------------------------


def test_rolling_reload_serves_old_until_new_warm(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    conf = {"serve.max_batch_size": 4, "serve.batch_buckets": [4],
            "serve.batch_deadline_ms": 1}
    with _deploy(est, x, replicas=2, conf=conf) as dep:
        import jax

        old_ref = _bucket_reference(est, x[:1], 4)
        # publish a NEW checkpoint with visibly different weights (epoch 99
        # sorts newest); empty opt_state exercises the inference loader's
        # no-optimizer contract too
        bumped = jax.tree.map(lambda a: np.asarray(a) * 1.5, est._params)
        est._save_checkpoint(bumped, 99, {})
        new_est = _make_estimator(ckpt_dir)
        new_est.load_latest_checkpoint()
        new_ref = _bucket_reference(new_est, x[:1], 4)
        assert not np.array_equal(old_ref, new_ref)

        responses = []
        stop = threading.Event()

        def stream():
            while not stop.is_set():
                responses.append(dep.predict(x[:1]))

        streamer = threading.Thread(target=stream)
        streamer.start()
        time.sleep(0.1)  # some traffic lands before the roll starts
        infos = dep.reload()
        time.sleep(0.1)
        stop.set()
        streamer.join()

        assert all(info["epoch"] == 99 for info in infos)
        # the atomic-generation contract: every response during the roll is
        # EXACTLY the old weights or EXACTLY the new — never torn state
        saw_old = saw_new = 0
        for out in responses:
            if np.array_equal(out, old_ref):
                saw_old += 1
            elif np.array_equal(out, new_ref):
                saw_new += 1
            else:
                pytest.fail("response matches neither old nor new weights")
        assert saw_old >= 1  # old weights served until the roll
        # after the roll completes, only the new weights serve
        assert np.array_equal(dep.predict(x[:1]), new_ref)
    # restore the module checkpoint state for later tests
    import shutil

    shutil.rmtree(os.path.join(ckpt_dir, "epoch_99"), ignore_errors=True)


def test_scale_out_in_counters_and_drain(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    with _deploy(est, x, replicas=1) as dep:
        out_before = obs.metrics.counter("serve.scale_out").value
        in_before = obs.metrics.counter("serve.scale_in").value
        dep.scale_to(2)
        assert dep.replica_count() == 2
        assert len(dep.batcher.live_replicas()) == 2
        assert obs.metrics.counter("serve.scale_out").value - out_before == 1
        # keep traffic flowing THROUGH the scale-in: graceful drain means
        # zero request errors while the victim leaves
        errors = []
        stop = threading.Event()

        def stream():
            while not stop.is_set():
                try:
                    dep.predict(x[:2])
                except Exception as exc:  # noqa: BLE001
                    errors.append(repr(exc))

        streamer = threading.Thread(target=stream)
        streamer.start()
        time.sleep(0.05)
        dep.scale_to(1)
        time.sleep(0.05)
        stop.set()
        streamer.join()
        assert not errors
        assert dep.replica_count() == 1
        assert obs.metrics.counter("serve.scale_in").value - in_before == 1
        # the drained replica is fully gone from dispatch accounting
        stats = dep.batcher.stats()
        assert stats["replicas"] == 1 and stats["draining"] == 0
        assert dep.predict(x[:1]).shape == (1, 1)


def test_autoscaler_sustained_signals_drive_scaling():
    """Policy unit test: injected signals through a fake deployment —
    sustained over-threshold scales out (never on one burst), sustained
    idle scales in, both bounded by min/max."""
    from raydp_tpu.serve.autoscaler import ServeController
    from raydp_tpu.serve.config import ServeConf

    class FakeDeployment:
        def __init__(self):
            self.replicas = 1
            self.calls = []

        def heal(self):
            return 0

        def replica_count(self):
            return self.replicas

        def scale_to(self, n):
            self.calls.append(n)
            self.replicas = n

        class _B:
            @staticmethod
            def inflight_total():
                return 0

        batcher = _B()

    conf = ServeConf(
        autoscale=True, min_replicas=1, max_replicas=3,
        sustained_ticks=3, target_queue_per_replica=4.0,
        slo_p99_ms=100.0, tick_s=3600.0,
    )
    dep = FakeDeployment()
    signals = {"queue_rows": 0.0, "inflight": 0, "p99_ms": 0.0}
    controller = ServeController(dep, conf, signal_fn=lambda: dict(signals))
    try:
        # one burst is NOT sustained: two hot ticks then a cold one
        signals["queue_rows"] = 40.0
        assert controller.tick() is None
        assert controller.tick() is None
        signals["queue_rows"] = 0.0
        signals["inflight"] = 1  # busy, not idle
        assert controller.tick() is None
        assert dep.calls == []
        # sustained backlog scales out
        signals["queue_rows"] = 40.0
        for _ in range(3):
            decision = controller.tick()
        assert decision == "out" and dep.replicas == 2
        # an SLO breach alone (queue empty) also counts as hot
        signals["queue_rows"] = 0.0
        signals["inflight"] = 1
        signals["p99_ms"] = 500.0
        for _ in range(3):
            decision = controller.tick()
        assert decision == "out" and dep.replicas == 3
        # bounded by max_replicas
        for _ in range(4):
            assert controller.tick() is None
        assert dep.replicas == 3
        # sustained idle drains back, bounded by min_replicas
        signals.update(queue_rows=0.0, inflight=0, p99_ms=0.0)
        decisions = [controller.tick() for _ in range(8)]
        assert decisions.count("in") == 2 and dep.replicas == 1
        assert controller.tick() is None  # min floor holds
    finally:
        controller.close()


# ---------------------------------------------------------------------------
# the request hot path: doorbell round trip
# ---------------------------------------------------------------------------


def test_doorbell_request_round_trip(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    with _deploy(est, x) as dep:
        before = obs.metrics.counter("serve.doorbell_pooled").value
        for _ in range(4):
            out = dep.predict(x[:2])
            assert out.shape == (2, 1)
        # after the first dispatch returned its socket to the dispatcher
        # thread's doorbell pool, subsequent requests ride pooled
        # connections — the PR 6 UDS fast path, observed end to end
        assert obs.metrics.counter("serve.doorbell_pooled").value > before
        assert dep.stats()["doorbell_pooled"] > 0


# ---------------------------------------------------------------------------
# zero-drop failover (the acceptance gate)
# ---------------------------------------------------------------------------


def test_replica_sigkill_mid_stream_drops_nothing(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    # a single fixed bucket makes every dispatch one shape, so the killed
    # and unkilled runs are comparable bit-for-bit (docs/serving.md)
    conf = {
        "serve.max_batch_size": 16,
        "serve.batch_buckets": [16],
        "serve.autoscale.tick_s": 0.1,
    }
    with _deploy(est, x, replicas=2, conf=conf) as dep:
        n_requests = 200

        def run_stream():
            results = [None] * n_requests
            errors = []

            def client(lo, hi):
                for i in range(lo, hi):
                    try:
                        results[i] = dep.predict(x[i % 1000 : i % 1000 + 1])
                    except Exception as exc:  # noqa: BLE001
                        errors.append(repr(exc))

            quarter = n_requests // 4
            threads = [
                threading.Thread(target=client,
                                 args=(k * quarter, (k + 1) * quarter))
                for k in range(4)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return results, errors

        clean, errors = run_stream()
        assert not errors and all(r is not None for r in clean)

        requeued_before = obs.metrics.counter(
            "serve.requeued_requests"
        ).value
        failovers_before = obs.metrics.counter(
            "serve.replica_replacements"
        ).value

        def killer():
            time.sleep(0.05)
            dep._handles[0].kill(no_restart=True)

        kt = threading.Thread(target=killer)
        kt.start()
        chaos, errors = run_stream()
        kt.join()
        # ZERO dropped requests, responses byte-identical to the unkilled run
        assert not errors
        assert all(r is not None for r in chaos)
        assert all(
            np.array_equal(a, b) for a, b in zip(clean, chaos)
        )
        # the controller heals the pool back to target: wait for the
        # replacement as an EVENT (its counter rises), then for the count.
        # The count alone also reads 2 before the controller has seen the
        # kill, while the dead replica is still in the pool
        replacements = obs.metrics.counter("serve.replica_replacements")
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline and (
            replacements.value <= failovers_before
            or dep.replica_count() < 2
        ):
            time.sleep(0.05)
        assert replacements.value > failovers_before
        assert dep.replica_count() == 2
        # in-flight loss shows up as re-admissions only when the kill landed
        # mid-dispatch; either way the counters moved without any drop
        assert obs.metrics.counter("serve.dropped_requests").value == 0
        del requeued_before  # evidence in the chaos scenario's report


def test_request_exceeding_max_batch_rejected(served_model):
    est, ckpt_dir, x, eval_ds = served_model
    with _deploy(est, x, conf={"serve.max_batch_size": 4}) as dep:
        with pytest.raises(ValueError, match="max_batch_size"):
            dep.predict(x[:8])
        # the deployment still serves admissible requests afterwards
        assert dep.predict(x[:2]).shape == (2, 1)


def test_deploy_raises_when_no_replica_reaches_the_platform(served_model):
    """One process per chip: a replica that comes up on another platform
    than the driver asked for (here "tpu" on a CPU box — on a TPU host, the
    CPU backend a second process silently gets when the first holds the
    chip and JAX_PLATFORMS is unset) is a failed spawn, and a deployment
    that starts with no replica raises instead of serving from nothing."""
    from raydp_tpu.cluster.common import ClusterError

    est, ckpt_dir, x, eval_ds = served_model
    with pytest.raises(ClusterError, match="came up on 'cpu'"):
        serve.deploy(est, example=x[:1], platform="tpu")
    # asking for the platform the replicas do reach deploys as before
    with serve.deploy(est, example=x[:1], platform="cpu") as dep:
        assert dep.predict(x[:2]).shape == (2, 1)
