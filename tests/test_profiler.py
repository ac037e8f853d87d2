"""Compute observatory tests (ISSUE 15): step-phase profiler, live MFU,
capture windows, memory watermark plane, and the bench-regression sentry.

The fit-level tests run the estimator against an in-memory host dataset —
the observatory instruments the train loop, not the ETL exchange, and a
clusterless fit keeps them fast and deterministic. The dossier test uses a
real cluster (the memory section is head-side state)."""

import glob
import json
import os

import numpy as np
import pytest

import raydp_tpu
from raydp_tpu import obs
from raydp_tpu.estimator import JaxEstimator
from raydp_tpu.obs import costmodel, profiler


def _mlp():
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            x = nn.relu(nn.Dense(32)(x))
            return nn.Dense(1)(x)

    return MLP()


_DIMS = (8, 32, 1)  # analytic layer dims matching _mlp


class _HostDs:
    """Minimal Dataset stand-in for _stage_host (to_numpy is the whole
    staging contract for a non-streaming fit)."""

    def __init__(self, feats, labels):
        self._f, self._l = feats, labels
        self.uuid = "test-profiler"
        self.blocks = []

    def to_numpy(self, feature_columns, label_column, feature_dtype,
                 label_dtype):
        return self._f.astype(feature_dtype), self._l.astype(label_dtype)


@pytest.fixture(scope="module")
def host_ds():
    rng = np.random.default_rng(5)
    feats = rng.random((2048, _DIMS[0])).astype(np.float32)
    labels = (feats @ rng.random(_DIMS[0])).astype(np.float32)
    return _HostDs(feats, labels)


def _single_device_mesh():
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:1]), ("data",))


def _make_est(**overrides):
    kwargs = dict(
        model=_mlp, optimizer="adam", loss="mse",
        feature_columns=[f"f{i}" for i in range(_DIMS[0])],
        label_column="y", batch_size=64, num_epochs=2,
        seed=3, mesh=_single_device_mesh(),
    )
    kwargs.update(overrides)
    return JaxEstimator(**kwargs)


# ---------------------------------------------------------------------------
# instrument satellites: gauge watermark mode + time-series max fan-out
# ---------------------------------------------------------------------------


def test_gauge_watermark_mode():
    from raydp_tpu.obs.metrics import Gauge

    plain = Gauge()
    plain.set(3.0)
    # plain gauges keep the pre-existing snapshot shape byte-identical
    assert plain.snapshot() == {"type": "gauge", "value": 3.0}
    marked = Gauge()
    marked.set_watermark(5.0)
    marked.set_watermark(2.0)
    snap = marked.snapshot()
    assert snap["value"] == 2.0 and snap["max"] == 5.0
    marked.set_watermark(9.0)
    assert marked.snapshot()["max"] == 9.0


def test_timeseries_max_fanout():
    from raydp_tpu.obs.timeseries import SeriesStore

    store = SeriesStore()
    store.ingest("driver:1", "driver", {
        "mem.rss_bytes": {"type": "gauge", "value": 10.0, "max": 50.0},
        "estimator.step.dispatch_ms": {
            "type": "histogram", "count": 4, "sum": 8.0, "min": 1.0,
            "max": 5.0, "mean": 2.0, "p50": 2.0, "p99": 5.0,
        },
    })
    names = store.series_names()
    assert "mem.rss_bytes" in names
    assert "mem.rss_bytes.max" in names
    assert "estimator.step.dispatch_ms.max" in names
    peak = store.query("mem.rss_bytes.max")
    assert peak and peak[0]["last"] == 50.0


# ---------------------------------------------------------------------------
# step profiler: phases present + sane after a real 2-epoch fit
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def per_step_fit(host_ds):
    """One real 2-epoch fit on the per-step loop path (scan_epochs=False),
    shared by the phase/attribution/MFU tests."""
    est = _make_est(scan_epochs=False)
    history = est.fit(host_ds)
    return est, history


def test_step_phase_histograms_present_and_sane(per_step_fit):
    est, history = per_step_fit
    assert len(history) == 2
    stats = est.fit_stats_
    steps_expected = 2 * (2048 // 64)
    # first (compile) step is excluded from the steady-state histograms
    assert stats["steps"] == steps_expected - 1
    phases = stats["step_phase_seconds"]
    assert set(phases) == {"ingest", "h2d", "dispatch", "sync"}
    assert phases["dispatch"] > 0.0
    # phases tile the measured step-loop wall: the sum must account for
    # (nearly) all of it — an uninstrumented gap shows up here first
    wall = stats["step_wall_s"]
    assert wall and wall > 0.0
    covered = sum(phases.values())
    assert 0.7 * wall <= covered <= 1.1 * wall, (covered, wall)
    # the registry carries the per-step histograms (scrapeable mid-fit)
    snap = obs.metrics.snapshot()
    assert "estimator.step.compute_ms" not in snap
    for phase in ("ingest", "h2d", "dispatch"):
        hist = snap[f"estimator.step.{phase}_ms"]
        assert hist["type"] == "histogram" and hist["count"] > 0
        assert hist["max"] >= hist["p50"] >= 0.0


def test_explain_last_fit_attribution(per_step_fit):
    est, _history = per_step_fit
    report = est.explain_last_fit()
    assert report["root"] == "estimator.fit"
    # acceptance gate: ≥0.9 of the fit's wall time lands in NAMED segments
    assert report["attributed_frac"] >= 0.9, report["text"]
    # the step-phase split surfaces real compute-plane categories: the
    # host's time inside the step calls, and the fences it waited at
    assert report["by_category"].get("dispatch", 0.0) > 0.0
    assert report["by_category"].get("sync", 0.0) > 0.0
    assert "compile" in report["by_category"]
    assert report["text"].startswith("critical path of estimator.fit")


def test_live_mfu_vs_analytic_parity(per_step_fit):
    est, _history = per_step_fit
    stats = est.fit_stats_
    flops_live = stats["flops_per_step"]
    assert flops_live, stats
    flops_analytic = costmodel.mlp_train_flops_per_step(64, _DIMS)
    ratio = flops_live / flops_analytic
    # XLA counts the optimizer/elementwise work the matmul-only analytic
    # convention ignores; same-step-described is the contract, not equality
    assert 0.5 <= ratio <= 2.0, (flops_live, flops_analytic)
    assert stats["mfu"] is not None and stats["mfu"] > 0.0
    assert stats["peak_source"] in ("tpu-table", "env", "nominal-cpu")
    assert obs.metrics.gauge("estimator.mfu").value == pytest.approx(
        stats["mfu"]
    )
    # the ratio is completed work over the wall time between two
    # observations of completion, never over the host's time in dispatches
    assert stats["steps_completed"] == 2 * (2048 // 64)
    wall = stats["flops_per_step"] * (stats["steps_completed"] - 1) / (
        stats["model_flops_per_sec"]
    )
    assert wall > stats["step_phase_seconds"]["dispatch"]


def test_scan_path_reports_same_flops(host_ds, per_step_fit):
    """The segment-scanned path must report the SAME FLOPs-per-step as the
    per-step loop (one accounting): the scan executable is opaque to cost
    analysis, so the single-step abstract lowering covers it."""
    est_scan = _make_est()  # default scan_epochs → the resident scan
    est_scan.fit(host_ds)
    per_step_est, _ = per_step_fit
    assert est_scan.fit_stats_["flops_per_step"] == pytest.approx(
        per_step_est.fit_stats_["flops_per_step"]
    )
    assert est_scan.fit_stats_["steps"] == 2 * (2048 // 64)


def test_mfu_series_reaches_local_mirror(per_step_fit):
    """The estimator.mfu gauge rides the flush tick into the windowed
    time-series mirror — what a head scrape would show."""
    obs.flush()
    series = obs.query_local_series("estimator.mfu", window_s=600.0)
    assert series, "estimator.mfu series missing from the local mirror"
    assert series[-1]["last"] > 0.0


def test_step_profiler_off_is_noop(host_ds):
    profiler.set_step_profiler(False)
    try:
        est = _make_est(scan_epochs=False, num_epochs=1)
        est.fit(host_ds)
        assert est.fit_stats_["profiler"] == "off"
        assert est.fit_stats_["step_phase_seconds"] == {}
    finally:
        profiler.set_step_profiler(True)


# ---------------------------------------------------------------------------
# completed steps, the epoch restart, stable device names (ISSUE 24)
# ---------------------------------------------------------------------------

_RUNNERS = {
    # the per-step loop
    "per_step": dict(scan_epochs=False),
    # staged data too large for the whole-epoch scan: 8-step segment scans
    # fed by the producer thread
    "segment_streamed": dict(scan_memory_limit=1, stream_scan_steps=8),
    # lax.scan epochs over the device-resident copy
    "resident_scan": dict(),
}


@pytest.mark.parametrize("runner", sorted(_RUNNERS))
def test_completed_steps_from_second_thread(host_ds, runner):
    """completed_steps() read mid-fit from another thread is monotone, never
    above the dispatched count, and equals it once the fit is over."""
    import threading

    est = _make_est(num_epochs=3, **_RUNNERS[runner])
    seen, stop = [], threading.Event()

    def watch():
        while not stop.is_set():
            completed = est.completed_steps()
            recorder = getattr(est, "_step_recorder", None)
            seen.append((completed, getattr(recorder, "steps_dispatched", 0)))

    counted_before = obs.metrics.counter("estimator.steps_completed").value
    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    try:
        # the evaluation keeps the resident runner off the one-dispatch
        # whole-fit path, and gives every epoch a closing fence
        est.fit(host_ds, host_ds)
    finally:
        stop.set()
        watcher.join(timeout=10)
    total = 3 * (2048 // 64)
    assert est.completed_steps() == total
    assert est._step_recorder.steps_dispatched == total
    assert est.fit_stats_["steps_completed"] == total
    assert obs.metrics.counter(
        "estimator.steps_completed"
    ).value - counted_before == total
    completed = [c for c, _ in seen]
    assert completed == sorted(completed)
    assert all(c <= d for c, d in seen), [p for p in seen if p[0] > p[1]][:3]
    if runner == "segment_streamed":
        names = {r["name"] for r in est.last_fit_records_}
        # the producer thread adopted the fit's context and collectors
        assert {"exchange.upload", "estimator.segment_wait",
                "estimator.dispatch", "exchange.stage"} <= names
        fit_id = next(r["id"] for r in est.last_fit_records_
                      if r["name"] == "estimator.fit")
        assert all(r["parent"] == fit_id for r in est.last_fit_records_
                   if r["name"] == "exchange.upload")


def test_restart_histogram_one_observation_per_epoch_boundary(host_ds):
    hist = obs.metrics.histogram("estimator.epoch.restart_ms")
    before = hist.count
    est = _make_est(num_epochs=4)
    est.fit(host_ds, host_ds)
    assert hist.count - before == 3
    restarts = [r for r in est.last_fit_records_
                if r["name"] == "estimator.restart"]
    assert len(restarts) == 3
    # a restart runs from one epoch's fence INTO the next epoch's span
    epochs = {r["args"]["epoch"]: r for r in est.last_fit_records_
              if r["name"] == "estimator.epoch"}
    for r in restarts:
        nxt = epochs[r["args"]["epoch"] + 1]
        assert r["ts"] <= nxt["ts"] < r["ts"] + max(r["dur"], 1) + 1
    # no evaluation and no sync fence: no closing fence, nothing observed
    est = _make_est(num_epochs=3, sync_every_steps=0, scan_epochs=False)
    before = hist.count
    est.fit(host_ds)
    assert hist.count == before


def test_compile_and_stage_counters_readable(host_ds):
    compile_c = obs.metrics.counter("estimator.compile_seconds")
    stage_c = obs.metrics.counter("exchange.stage_seconds")
    before = compile_c.value, stage_c.value
    est = _make_est(num_epochs=1)
    est._stage_cache = {}
    est.fit(_HostDs(host_ds._f.copy(), host_ds._l.copy()))
    assert compile_c.value - before[0] == pytest.approx(est.compile_seconds_)
    whats = {r["args"]["what"] for r in est.last_fit_records_
             if r["name"] == "estimator.compile"}
    assert {"init", "flops_probe"} <= whats
    assert stage_c.value > before[1]
    assert "estimator.compile_s" not in obs.metrics.snapshot()


def test_step_hlo_carries_scope_names(monkeypatch):
    """The table update, the loss/gradient and the interaction call have
    stable names in the compiled step: what a trace reader finds them by."""
    import jax

    from raydp_tpu.models import DLRM

    lowered = {}

    def probe(fn, *args):
        lowered["text"] = jax.jit(fn).lower(*args).compile().as_text()
        return 1.0

    monkeypatch.setattr(costmodel, "step_flops_abstract", probe)
    vocab = (11, 7)
    rng = np.random.default_rng(0)
    dense = rng.random((256, 3)).astype(np.float32)
    ids = np.stack([rng.integers(0, v, 256) for v in vocab], 1)

    class _Grouped(_HostDs):
        def to_numpy_grouped(self, groups, label_column, label_dtype):
            return (dense, ids.astype(np.int32)), self._l.astype(label_dtype)

    est = JaxEstimator(
        model=DLRM(vocab_sizes=vocab, num_dense=3, embed_dim=4,
                   bottom_mlp=(8, 4), top_mlp=(8, 1),
                   use_pallas_interaction=False),
        optimizer="adagrad", loss="bce",
        feature_columns=["i0", "i1", "i2", "c0", "c1"],
        categorical_columns=["c0", "c1"], label_column="y", batch_size=64,
        num_epochs=1, seed=1, mesh=_single_device_mesh(),
    )
    est.fit(_Grouped(dense, (rng.random(256) > 0.5).astype(np.float32)))
    for scope in ("loss_and_grad", "optimizer_update", "dlrm_interaction"):
        assert scope in lowered["text"], scope


def test_span_lies_in_profiler_trace_host_plane(tmp_path):
    """A program span opened while a jax.profiler session runs is an event
    of its own name in the trace's host plane (the device trace's clock)."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        with obs.collect() as got:
            with obs.span("estimator.test_bridge", k=1):
                jnp.ones((8,)).block_until_ready()
            detached = obs.span("estimator.test_detached").start()
            with obs.span("estimator.test_inner"):
                pass
            detached.finish()
    # the span's own record is what it always was
    record = next(r for r in got if r["name"] == "estimator.test_bridge")
    assert record["args"] == {"k": 1} and record["dur"] >= 0
    inner = next(r for r in got if r["name"] == "estimator.test_inner")
    outer = next(r for r in got if r["name"] == "estimator.test_detached")
    assert inner["parent"] != outer["id"]  # start() installs no context
    (path,) = glob.glob(
        os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")
    )
    host_events = {
        ev.name
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
    }
    assert {"estimator.test_bridge", "estimator.test_detached",
            "estimator.test_inner"} <= host_events


# ---------------------------------------------------------------------------
# capture window
# ---------------------------------------------------------------------------


def test_profile_fit_capture_window(host_ds, tmp_path):
    est = _make_est(scan_epochs=False, num_epochs=1)
    out_dir = str(tmp_path / "cap")
    with profiler.profile_fit(steps=8, out_dir=out_dir,
                              jax_trace=False) as cap:
        est.fit(host_ds)
    result = cap.result()
    # span-only capture is the CPU floor: the fit's span records were
    # collected and written even with the deep trace unavailable/off
    assert result["span_records"] >= 3  # fit + epoch + compile at least
    assert result["spans_path"] and os.path.exists(result["spans_path"])
    with open(result["spans_path"]) as f:
        names = {record["name"] for record in json.load(f)}
    assert "estimator.fit" in names and "estimator.epoch" in names
    # the estimator drove the step budget
    assert result["steps_captured"] == 2048 // 64
    # the window is released: a second capture arms cleanly
    with profiler.capture(out_dir=str(tmp_path / "cap2"), jax_trace=False):
        pass


def test_capture_window_exclusive(tmp_path):
    with profiler.capture(out_dir=str(tmp_path / "a"), jax_trace=False):
        with pytest.raises(RuntimeError):
            with profiler.capture(out_dir=str(tmp_path / "b"),
                                  jax_trace=False):
                pass


# ---------------------------------------------------------------------------
# memory watermark plane
# ---------------------------------------------------------------------------


def test_memory_sampler_gauges_and_series():
    sample = profiler.sample_memory(force=True)
    assert sample is not None
    assert sample["rss_bytes"] > 0
    assert 0.0 <= sample["pressure"] <= 1.0
    snap = obs.metrics.snapshot()
    rss = snap["mem.rss_bytes"]
    assert rss["type"] == "gauge" and rss["max"] >= rss["value"] > 0
    # the flush tick fans the watermark out as a .max series in the mirror
    obs.flush()
    assert obs.query_local_series("mem.rss_bytes", window_s=600.0)
    assert obs.query_local_series("mem.rss_bytes.max", window_s=600.0)
    # the controllers' read
    assert 0.0 <= profiler.current_mem_pressure() <= 1.0


def test_memory_sampler_throttles():
    assert profiler.sample_memory(force=True) is not None
    # immediately after a forced sample the throttle window is closed
    assert profiler.sample_memory() is None


def test_autoscaler_vetoes_scale_out_under_mem_pressure():
    """Policy unit (injected signals, no cluster): a sustained-hot
    deployment must NOT scale out while mem_pressure exceeds the conf
    ceiling — and must scale out once pressure clears."""
    from raydp_tpu.serve.autoscaler import ServeController
    from raydp_tpu.serve.config import ServeConf

    class FakeDeployment:
        def __init__(self):
            self.scaled_to = []

        def heal(self):
            return 0

        def replica_count(self):
            return 1

        def scale_to(self, n):
            self.scaled_to.append(n)

    conf = ServeConf(autoscale=True, sustained_ticks=1, max_replicas=4,
                     tick_s=3600.0, max_mem_pressure=0.9)
    dep = FakeDeployment()
    signals = {"queue_rows": 100.0, "inflight": 1, "p99_ms": 0.0,
               "mem_pressure": 0.99}
    controller = ServeController(dep, conf, signal_fn=lambda: dict(signals))
    try:
        assert controller.tick() is None  # hot but vetoed
        assert dep.scaled_to == []
        assert (
            obs.metrics.counter("serve.scale_out_vetoed_mem").value >= 1
        )
        signals["mem_pressure"] = 0.1
        assert controller.tick() == "out"  # pressure cleared
        assert dep.scaled_to == [2]
    finally:
        controller.close()


def test_dossier_memory_section_on_sigkill():
    """Acceptance: a SIGKILLed executor's crash dossier carries the memory
    watermark plane — per-process mem.* gauges (live + max) shipped with
    the victims' flush ticks land in the head section."""
    import time

    from raydp_tpu.cluster import api as cluster
    from raydp_tpu.etl import functions as F

    session = raydp_tpu.init_etl(
        "prof-dossier", num_executors=2, executor_cores=1,
        executor_memory="300M",
    )
    try:
        df = session.range(30_000, num_partitions=4).with_column(
            "v", F.col("id") + 1
        )
        assert df.count() == 30_000
        victim = session.executors[0]
        victim_id = victim.actor_id
        victim.kill(no_restart=True)
        dossier_dir = os.path.join(cluster.session_dir(), "dossiers")
        deadline = time.monotonic() + 10.0
        found = None
        while time.monotonic() < deadline and found is None:
            for path in sorted(glob.glob(
                os.path.join(dossier_dir, "dossier-*.json")
            )):
                with open(path) as f:
                    dossier = json.load(f)
                if dossier["victim"].get("actor_id") == victim_id:
                    found = dossier
                    break
            time.sleep(0.1)
        assert found is not None, "no dossier written for the victim"
        memory = found["head"].get("memory")
        assert memory, "dossier head section carries no memory plane"
        # every recorded process entry is mem.* gauges with value + max
        some = next(iter(memory.values()))
        assert any(k.startswith("mem.") for k in some)
        rss = some.get("mem.rss_bytes")
        assert rss and rss["value"] > 0 and rss["max"] >= rss["value"]
    finally:
        session.stop()


# ---------------------------------------------------------------------------
# cost model units
# ---------------------------------------------------------------------------


def test_costmodel_peak_sources(monkeypatch):
    monkeypatch.setenv(costmodel.PEAK_FLOPS_ENV, "123e12")
    info = costmodel.device_peak_flops()
    assert info["peak"] == 123e12 and info["peak_source"] == "env"
    monkeypatch.delenv(costmodel.PEAK_FLOPS_ENV)
    info = costmodel.device_peak_flops()
    # CPU test boxes get the nominal estimate so the MFU gauge exists
    assert info["peak_source"] in ("nominal-cpu", "tpu-table")
    assert info["peak"] and info["peak"] > 0


def test_costmodel_analytic_flops():
    # lm accounting unchanged from the bench's original (the bench imports
    # THIS function now — one accounting)
    per_token = 2 * (24 * 128**2 + 2 * 128 * (64 + 1)) + 2 * 128 * 1000
    assert costmodel.lm_train_flops_per_step(4, 64, 128, 2, 1000) == (
        3 * 4 * 64 * per_token
    )
    assert costmodel.mlp_train_flops_per_step(32, (8, 16, 1)) == (
        3 * 2 * 32 * (8 * 16 + 16 * 1)
    )
    assert costmodel.mfu(None, 1.0) is None
    assert costmodel.mfu(5.0, 10.0) == 0.5
