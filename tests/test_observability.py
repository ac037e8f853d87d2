"""Observability plane tests: span API, metrics registry, trace propagation
across processes, Perfetto export round-trip, last_query_stats schema.

Real multi-process sessions (no mocks), like the rest of the suite: the
export test asserts spans collected from MULTIPLE processes land in one
Perfetto-loadable JSON under a shared trace id.
"""

import json
import os
import tempfile

import pytest

import raydp_tpu
from raydp_tpu import obs
from raydp_tpu.etl import functions as F
from raydp_tpu.obs import tracing


# ---------------------------------------------------------------------------
# unit: span / collector / metrics primitives (no cluster needed)
# ---------------------------------------------------------------------------


def test_span_disabled_fast_path_is_noop():
    assert not tracing.enabled() or os.environ.get("RAYDP_TPU_TRACE")
    tracing.set_enabled(False)
    s = obs.span("x", a=1)
    assert s is tracing._NOOP
    # no-op spans are context managers with a zero duration and a set() sink
    with s as entered:
        entered.set(b=2)
    assert s.duration == 0.0
    # ... and answer the detached form too, still as the shared object
    assert s.start() is tracing._NOOP
    s.finish()
    assert s.duration == 0.0


def test_span_without_jax_never_imports_it():
    """A real span in a process that has not imported jax works, and obs is
    not what drags jax in (the profiler bridge looks in sys.modules only)."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from raydp_tpu import obs\n"
        "from raydp_tpu.obs import tracing\n"
        "with obs.collect() as got:\n"
        "    with obs.span('etl.stage', n=1) as s:\n"
        "        pass\n"
        "    d = obs.span('etl.detached').start()\n"
        "    d.finish()\n"
        "assert [r['name'] for r in got] == ['etl.stage', 'etl.detached']\n"
        "assert s._annotation is None and tracing._trace_annotation is None\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n"
        "print('ok')\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", code], cwd=repo, capture_output=True,
        text=True, timeout=60,
    )
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_collector_captures_spans_and_instants():
    with obs.collect() as got:
        with obs.span("outer", k="v"):
            with obs.span("inner"):
                pass
            obs.instant("marker", n=3)
    names = [r["name"] for r in got]
    # children finish (and record) before their parents
    assert names == ["inner", "marker", "outer"]
    outer = got[-1]
    inner = got[0]
    marker = got[1]
    assert inner["trace"] == outer["trace"] == marker["trace"]
    assert inner["parent"] == outer["id"]
    assert marker["parent"] == outer["id"]
    assert outer["args"]["k"] == "v"
    assert outer["dur"] >= inner["dur"] >= 0


def test_collectors_nest_independently():
    with obs.collect() as outer_got:
        with obs.span("a"):
            pass
        with obs.collect() as inner_got:
            with obs.span("b"):
                pass
    assert [r["name"] for r in inner_got] == ["b"]
    assert [r["name"] for r in outer_got] == ["a", "b"]


def test_metrics_registry_snapshot():
    from raydp_tpu.obs.metrics import Registry

    reg = Registry()
    reg.counter("c").inc()
    reg.counter("c").inc(2.5)
    reg.gauge("g").set(7)
    for v in (1.0, 3.0):
        reg.histogram("h").observe(v)
    snap = reg.snapshot()
    assert snap["c"] == {"type": "counter", "value": 3.5}
    assert snap["g"] == {"type": "gauge", "value": 7.0}
    assert snap["h"]["count"] == 2 and snap["h"]["sum"] == 4.0
    assert snap["h"]["min"] == 1.0 and snap["h"]["max"] == 3.0
    with pytest.raises(TypeError):
        reg.gauge("c")  # type confusion must fail loudly


def test_histogram_reservoir_quantiles():
    """The bounded-reservoir quantile estimator (serving SLO gauges): exact
    nearest-rank while observations fit the reservoir, fixed memory beyond,
    OFF (no allocation) until the first observe, and the pre-quantile
    snapshot fields byte-compatible for old readers."""
    from raydp_tpu.obs.metrics import Histogram

    h = Histogram()
    # off until first observe: no reservoir allocated, empty snapshot is
    # byte-identical to the pre-quantile shape
    assert h._reservoir is None
    assert h.snapshot() == {"type": "histogram", "count": 0, "sum": 0.0}
    assert h.quantile(0.5) is None

    for v in range(100):  # 0..99: exact regime (fits the reservoir)
        h.observe(float(v))
    snap = h.snapshot()
    # additive keys only; the old fields carry their old values
    assert snap["count"] == 100 and snap["min"] == 0.0 and snap["max"] == 99.0
    assert snap["p50"] == 50.0 and snap["p99"] == 99.0

    # beyond the reservoir: memory stays fixed, the estimate stays sane
    for v in range(100, 20_000):
        h.observe(float(v))
    assert len(h._reservoir) == Histogram.RESERVOIR_SIZE
    snap = h.snapshot()
    assert snap["count"] == 20_000
    # a uniform sample of 0..19999: p50 near 10k, p99 in the top decile
    assert 5_000 < snap["p50"] < 15_000
    assert snap["p99"] > 15_000


def test_ring_buffer_bounded_and_drop_counted():
    tracing.set_enabled(True)
    try:
        tracing.drain_local()
        cap = tracing._buffer.maxlen
        before_dropped = tracing.dropped_count()
        for i in range(cap + 7):
            tracing._buffer_append({"name": f"s{i}", "ts": 0, "dur": 0,
                                    "pid": 0, "tid": 0})
        assert len(tracing._buffer) <= cap
        assert tracing.dropped_count() >= before_dropped
    finally:
        tracing.drain_local()
        tracing.set_enabled(False)


# ---------------------------------------------------------------------------
# integration: traced session → head aggregation → Perfetto export
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def traced_session():
    tracing.set_enabled(True)
    os.environ["RAYDP_TPU_TRACE"] = "1"
    s = raydp_tpu.init_etl(
        "test-obs", num_executors=2, executor_cores=1,
        executor_memory="300M",
        # executors may join a cluster whose head predates this module —
        # enable tracing in their spawn env explicitly
        configs={"etl.actor.env.RAYDP_TPU_TRACE": "1"},
    )
    yield s
    raydp_tpu.stop_etl()
    tracing.set_enabled(False)
    os.environ.pop("RAYDP_TPU_TRACE", None)


def test_last_query_stats_schema(traced_session):
    """The stats schema downstream consumers (bench etl_breakdown, docs)
    rely on: stable top-level keys, stable per-stage keys, fusion entries."""
    df = (
        traced_session.range(200, num_partitions=4)
        .with_column("x", F.col("id") * 2)
        .with_column("y", F.col("x") + 1)
        .select("id", "y")
    )
    table = df.to_arrow()
    assert table.num_rows == 200
    stats = traced_session.last_query_stats
    assert set(stats) == {
        "seconds", "output_partitions", "stages", "fusion", "shuffle",
        "plan_cache", "rpc", "recovery",
    }
    assert stats["seconds"] > 0
    assert stats["output_partitions"] >= 1
    assert stats["stages"], "at least one stage must be recorded"
    assert stats["shuffle"] == []  # narrow-only query: no exchange ran
    # per-query control-plane accounting (the millisecond-control-plane
    # numbers): plan-cache outcome + RPC round-trip counts
    assert {"hits", "misses", "unsupported", "hit"} <= set(stats["plan_cache"])
    assert {"head_rpcs", "actor_dispatches", "head_bypass_hits"} <= set(
        stats["rpc"]
    )
    assert stats["rpc"]["actor_dispatches"] >= 1
    # lineage-recovery accounting (docs/fault_tolerance.md): both keys are
    # PINNED and zero on a healthy query — the happy path pays no recovery
    assert set(stats["recovery"]) == {"reexecuted_tasks", "recovered_blocks"}
    assert stats["recovery"]["reexecuted_tasks"] == 0
    assert stats["recovery"]["recovered_blocks"] == 0
    for stage in stats["stages"]:
        # per-stage schema: task count, wall seconds, locality + dispatch
        # mode, and the server-side read/compute/emit phase split
        # (reduce stages dispatched barrier-free report "pipelined" and
        # carry no locality count — their dispatch happened inside the map
        # stage's gather loop)
        assert {"tasks", "seconds", "dispatch",
                "server_seconds", "read_s", "compute_s", "emit_s"} <= set(
            stage
        ), stage
        assert stage["dispatch"] in (
            "per_task", "batched", "pipelined", "fused", "fused_failed",
            "compiled", "compiled_fused", "compiled_failed",
        )
        if stage["dispatch"] in ("per_task", "batched", "compiled"):
            assert "locality_preferred" in stage
        assert stage["tasks"] >= 1
        assert stage["seconds"] >= 0
    # two adjacent Projects fused into one → a recorded fusion decision
    assert stats["fusion"], stats
    for decision in stats["fusion"]:
        assert {"narrow_ops", "fused_ops"} <= set(decision)
        assert decision["fused_ops"] < decision["narrow_ops"]


def test_export_trace_perfetto_round_trip(traced_session):
    """export_trace output is valid JSON in the Chrome trace-event format
    Perfetto loads: every event carries ph/ts/pid/tid/name, spans from more
    than one process appear, and a driver stage span and an executor task
    span link under ONE trace id."""
    df = traced_session.range(500, num_partitions=6).with_column(
        "z", F.col("id") + 1
    )
    assert df.count() == 500
    path = os.path.join(tempfile.mkdtemp(), "trace.json")
    out = raydp_tpu.export_trace(path)
    assert out == path
    with open(path) as f:
        doc = json.load(f)
    events = doc["traceEvents"]
    assert events, "trace must contain events"
    for event in events:
        for key in ("ph", "ts", "pid", "tid", "name"):
            assert key in event, (key, event)
    complete = [e for e in events if e["ph"] == "X"]
    assert complete, "no complete spans in trace"
    for event in complete:
        assert "dur" in event
    # process-name metadata gives each runtime process a labeled track
    meta = [e for e in events if e["ph"] == "M"]
    assert meta and all(e["name"] == "process_name" for e in meta)
    # spans from >1 process (driver + at least one executor actor)
    pids = {e["pid"] for e in complete}
    assert len(pids) >= 2, f"expected multi-process trace, got pids={pids}"
    # causal link: executor-side task spans carry the DRIVER's trace id
    stage = [e for e in complete if e["name"] == "etl.stage"]
    tasks = [e for e in complete if e["name"] == "task.run"]
    assert stage and tasks
    stage_traces = {e["args"]["trace_id"] for e in stage}
    assert any(
        e["args"]["trace_id"] in stage_traces for e in tasks
    ), "executor task spans not linked to a driver stage trace"


def test_dump_metrics_merges_processes(traced_session):
    df = traced_session.range(300, num_partitions=4).with_column(
        "w", F.col("id") * 3
    )
    assert df.count() == 300
    merged = raydp_tpu.dump_metrics()
    assert merged, "no metrics collected"
    # driver registry present and counting RPCs
    driver_keys = [k for k in merged if k.startswith("driver:")]
    assert driver_keys
    assert merged[driver_keys[0]]["rpc.client.calls"]["value"] > 0
    # at least one worker process flushed its registry (tasks ran there)
    flat = {
        name for snap in merged.values() for name in snap
    }
    assert "etl.tasks_run" in flat


def test_recovery_and_elasticity_counters_in_dump_metrics(traced_session):
    """The fault-tolerance counters are part of the pinned metrics surface:
    retry/recovery/scaling activity must be attributable from
    dump_metrics() alone (zero-valued when nothing failed — the session
    touches them at boot exactly so the keys always exist)."""
    assert traced_session.range(100, num_partitions=2).count() == 100
    merged = raydp_tpu.dump_metrics()
    driver_key = next(k for k in merged if k.startswith("driver:"))
    snap = merged[driver_key]
    for name in (
        "etl.task_retries",
        "lineage.reexecuted_tasks",
        "lineage.recovered_blocks",
        "cluster.scale_out",
        "cluster.scale_in",
    ):
        assert name in snap, name
        assert snap[name]["type"] == "counter"
        assert snap[name]["value"] >= 0


def test_trace_disabled_leaves_stats_working(traced_session):
    """With tracing off, query stats still derive from (collector-only)
    spans — the obs layer is the one timing source either way."""
    tracing.set_enabled(False)
    try:
        df = traced_session.range(100, num_partitions=2).with_column(
            "q", F.col("id") + 5
        )
        assert df.count() == 100
        stats = traced_session.last_query_stats
        assert stats["stages"] and stats["seconds"] > 0
    finally:
        tracing.set_enabled(True)


# ---------------------------------------------------------------------------
# telemetry plane v2: time-series store, scrape endpoint, query_metrics
# ---------------------------------------------------------------------------


def test_timeseries_store_counters_gauges_histograms():
    """SeriesStore unit semantics: counters keep cumulative points with a
    windowed delta, gauges keep sampled values, histograms fan out, and
    tenant.<ns>.<metric> series normalize under a tenant label."""
    import time

    from raydp_tpu.obs.timeseries import SeriesStore

    store = SeriesStore()
    t0 = time.time() - 60.0  # recent: query windows are wall-clock trailing
    for i, value in enumerate((3.0, 7.0, 12.0)):
        store.ingest(
            "driver:1", "driver",
            {
                "c": {"type": "counter", "value": value},
                "g": {"type": "gauge", "value": value * 10},
                "h": {"type": "histogram", "count": i + 1, "sum": value,
                      "min": 1.0, "max": value, "mean": value, "p50": value,
                      "p99": value},
                "tenant.appa.queue_depth": {"type": "gauge", "value": i},
            },
            ts=t0 + i,
        )
    counter = store.query("c", window_s=1e9)
    assert len(counter) == 1
    assert counter[0]["last"] == 12.0 and counter[0]["delta"] == 9.0
    assert counter[0]["labels"]["role"] == "driver"
    gauge = store.query("g", window_s=1e9)
    assert gauge[0]["last"] == 120.0 and "delta" not in gauge[0]
    # histogram fan-out: count/sum cumulative + quantile gauges
    assert store.query("h.count", 1e9)[0]["last"] == 3
    assert store.query("h.p99", 1e9)[0]["last"] == 12.0
    # tenant normalization: one series family, tenant as a label
    tenant = store.query("tenant.queue_depth", 1e9,
                         labels={"tenant": "appa"})
    assert tenant and tenant[0]["labels"]["tenant"] == "appa"
    # windowed aggregate shape
    agg = store.windowed("c", window_s=1e9)
    assert agg["series"] == 1 and agg["delta"] == 9.0


def test_timeseries_windowed_query_under_concurrent_flushers():
    """query_metrics correctness while many threads ingest concurrently:
    no lost reads/raises, and each proc's counter series stays monotone
    with an exact final delta."""
    import threading
    import time

    from raydp_tpu.obs.timeseries import SeriesStore

    store = SeriesStore()
    n_threads, n_ticks = 6, 40
    base = time.time() - 3600.0
    errors = []

    def flusher(idx: int) -> None:
        try:
            for tick in range(n_ticks):
                store.ingest(
                    f"worker:a{idx}:{idx}", f"worker:a{idx}",
                    {"etl.tasks_run": {"type": "counter",
                                       "value": float(tick + 1)}},
                    ts=base + tick,  # distinct points (no interval fold)
                )
        except Exception as exc:  # noqa: BLE001 - the gate reports it
            errors.append(repr(exc))

    def reader() -> None:
        try:
            for _ in range(200):
                store.query("etl.tasks_run", window_s=1e9)
                store.windowed("etl.tasks_run", window_s=1e9)
                store.prometheus_text()
        except Exception as exc:  # noqa: BLE001 - the gate reports it
            errors.append(repr(exc))

    threads = [
        threading.Thread(target=flusher, args=(i,)) for i in range(n_threads)
    ] + [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors, errors
    series = store.query("etl.tasks_run", window_s=1e9)
    assert len(series) == n_threads
    for entry in series:
        points = [v for _, v in entry["points"]]
        assert points == sorted(points), "counter series must be monotone"
        assert entry["last"] == float(n_ticks)
        assert entry["delta"] == float(n_ticks - 1)
    agg = store.windowed("etl.tasks_run", window_s=1e9)
    assert agg["last"] == float(n_threads * n_ticks)


def test_prometheus_text_round_trip_unit():
    from raydp_tpu.obs.timeseries import SeriesStore, parse_prometheus_text

    store = SeriesStore()
    store.ingest(
        "driver:9", "driver",
        {
            "serve.requests": {"type": "counter", "value": 41.0},
            "tenant.app-x.queue_depth": {"type": "gauge", "value": 3.0},
        },
        ts=123.0,
    )
    parsed = parse_prometheus_text(store.prometheus_text())
    assert parsed["raydp_serve_requests_total"][
        (("proc", "driver:9"), ("role", "driver"))
    ] == 41.0
    tenant_series = parsed["raydp_tenant_queue_depth"]
    labels = next(iter(tenant_series))
    assert ("tenant", "app-x") in labels
    assert tenant_series[labels] == 3.0


def test_scrape_endpoint_round_trip(traced_session):
    """Live scrape → parse → values match dump_metrics: the endpoint is
    started on the running head via the obs_configure op, one real TCP
    scrape parses in the exposition format, carries per-tenant labels, and
    the driver's counter values agree exactly with dump_metrics."""
    from raydp_tpu.cluster import api as cluster
    from raydp_tpu.obs.timeseries import parse_prometheus_text, scrape

    assert traced_session.range(100, num_partitions=2).count() == 100
    settings = cluster.head_rpc("obs_configure", scrape_port=0)
    host, port = settings["scrape_addr"]
    obs.flush()  # the driver's registry must be on the head before scraping
    text = scrape(host, port)
    parsed = parse_prometheus_text(text)
    assert parsed, "scrape did not parse"
    merged = raydp_tpu.dump_metrics()
    driver_key = next(k for k in merged if k.startswith("driver:"))
    sessions_started = merged[driver_key]["etl.sessions_started"]["value"]
    prom = parsed["raydp_etl_sessions_started_total"]
    driver_labels = next(
        labels for labels in prom if ("proc", driver_key) in labels
    )
    assert prom[driver_labels] == sessions_started
    # per-tenant labels: the session registered as a named tenant, so its
    # tenant.* series carry tenant="<ns>"
    tenant_labeled = [
        labels
        for name, series in parsed.items() if name.startswith("raydp_tenant_")
        for labels in series
        if any(k == "tenant" for k, _ in labels)
    ]
    assert tenant_labeled, "no tenant-labeled series in scrape"


def test_query_metrics_windowed(traced_session):
    """cluster.query_metrics returns windowed series from the head TSDB:
    worker-side task counters with cumulative points + window deltas, and
    the aggregate flavor sums across processes."""
    from raydp_tpu.cluster import api as cluster

    before = cluster.query_metrics(
        "etl.tasks_run", window_s=600.0, aggregate=True
    )
    assert traced_session.range(400, num_partitions=4).count() == 400
    series = cluster.query_metrics("etl.tasks_run", window_s=600.0)
    workers = [e for e in series if e["labels"]["role"] == "worker"]
    assert workers, series
    for entry in workers:
        assert entry["type"] == "counter"
        assert entry["points"] and entry["last"] >= 1
    after = cluster.query_metrics(
        "etl.tasks_run", window_s=600.0, aggregate=True
    )
    assert after["last"] >= before.get("last", 0) + 4, (before, after)


def test_head_ring_conf_and_eviction_counters(traced_session):
    """Satellite: the head span-ring capacity is a conf (obs.head_ring_spans
    via obs_configure), and evictions are counted PER ROLE in the head's
    registry — visible in dump_metrics, never silent."""
    from raydp_tpu.cluster import api as cluster

    original = cluster.head_rpc("obs_configure")["head_ring_spans"]
    try:
        small = cluster.head_rpc("obs_configure", head_ring_spans=8)
        assert small["head_ring_spans"] == 8
        span = {"name": "synthetic", "ts": 0, "dur": 1, "pid": 7,
                "tid": 0, "proc": "worker:actor-synth", "trace": "t",
                "id": "s", "parent": None, "args": {}}
        for batch in range(4):
            cluster.head_rpc(
                "obs_ingest",
                proc={"role": "worker:actor-synth", "pid": 7},
                spans=[dict(span, id=f"s{batch}-{i}") for i in range(8)],
                metrics_snapshot={},
            )
        merged = raydp_tpu.dump_metrics()
        head_key = next(k for k in merged if k.startswith("head:"))
        evictions = {
            name: snap["value"]
            for name, snap in merged[head_key].items()
            if name.startswith("obs.ingest_evictions.")
        }
        assert evictions.get("obs.ingest_evictions.worker", 0) >= 8, merged[
            head_key
        ].keys()
    finally:
        cluster.head_rpc("obs_configure", head_ring_spans=original)


# ---------------------------------------------------------------------------
# critical-path analyzer
# ---------------------------------------------------------------------------


def _span(name, ts, dur, sid, parent=None, trace="t1", proc="driver",
          **args):
    return {"name": name, "ts": ts, "dur": dur, "pid": 1, "tid": 1,
            "proc": proc, "trace": trace, "id": sid, "parent": parent,
            "args": args}


def test_critical_path_analyzer_white_box():
    """Synthetic span graph with a KNOWN critical path: the last-finisher
    chain must attribute each interval to the right category, surface the
    engineered stall, and cover the root's whole wall time."""
    from raydp_tpu.obs.analysis import attribute

    # root query 0..100ms; stage A 0..40 (two concurrent tasks, the longer
    # one 5..38 on the critical path); a 10ms engineered stall 40..50; stage
    # B 50..95 with phase args; 95..100 driver tail
    records = [
        _span("etl.query", 0, 100_000, "root"),
        _span("etl.stage", 0, 40_000, "stageA", parent="root"),
        _span("executor.task", 2_000, 20_000, "taskA1", parent="stageA",
              proc="worker:a"),
        _span("executor.task", 5_000, 33_000, "taskA2", parent="stageA",
              proc="worker:b"),
        _span("etl.stage", 50_000, 45_000, "stageB", parent="root",
              server_seconds=0.040, read_s=0.010, compute_s=0.025,
              emit_s=0.005),
    ]
    report = attribute(records, root_name="etl.query")
    assert report["total_s"] == pytest.approx(0.100)
    # every microsecond of the root lands in exactly one segment
    assert sum(s["dur_s"] for s in report["segments"]) == pytest.approx(
        0.100, rel=1e-6
    )
    by_cat = report["by_category"]
    # stage B's phase split: 5ms dispatch envelope + 10/25/5 read/compute/emit
    assert by_cat["decode"] == pytest.approx(0.010, abs=2e-4)
    assert by_cat["rpc"] == pytest.approx(0.005, abs=2e-4)
    # compute: taskA2's 33ms on the chain + taskA1's leading 3ms (2..5)
    # + stage B's 25ms
    assert by_cat["compute"] == pytest.approx(0.061, abs=5e-4)
    # the engineered inter-stage stall (40..50) lands on the root's self
    # time ("driver") and in the widest-stall report
    assert by_cat["driver"] >= 0.010
    stalls = report["stalls"]
    assert stalls and stalls[0]["owner"] == "etl.query"
    assert stalls[0]["dur_s"] == pytest.approx(0.010, abs=1e-4)
    assert stalls[0]["after"] == "etl.stage"
    # everything here is named — nothing fell to the "other" bucket
    assert report["attributed_frac"] == pytest.approx(1.0)
    assert "other" not in by_cat


def test_explain_last_query_attribution(traced_session):
    """The acceptance gate: explain_last_query attributes >=90% of a
    SHUFFLE query's wall time to named critical-path segments, and the
    report carries the category split + widest stalls."""
    df = traced_session.range(60_000, num_partitions=4).with_column(
        "k", F.col("id") % 13
    )
    assert df.group_by("k").count().to_arrow().num_rows == 13
    report = raydp_tpu.explain_last_query()
    assert report["root"] == "etl.query"
    assert report["attributed_frac"] >= 0.90, report["by_category"]
    assert report["total_s"] > 0
    named = set(report["by_category"])
    assert named & {"compute", "dispatch", "rpc", "decode"}, named
    assert "text" in report and "critical path of etl.query" in report["text"]
    # session-method flavor returns the same shape
    assert traced_session.explain_last_query()["root"] == "etl.query"


# ---------------------------------------------------------------------------
# serve request-path tracing
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served_model(traced_session):
    """A tiny fitted model deployed on the traced cluster with every
    request sampled (obs.request_sample_rate=1.0)."""
    import tempfile

    import numpy as np
    import pandas as pd

    from raydp_tpu import serve
    from raydp_tpu.estimator import JaxEstimator
    from raydp_tpu.models import MLPRegressor

    rng = np.random.default_rng(2)
    pdf = pd.DataFrame({
        "a": rng.random(192).astype(np.float32),
        "b": rng.random(192).astype(np.float32),
    })
    pdf["y"] = 2 * pdf["a"] + 3 * pdf["b"]
    est = JaxEstimator(
        model=MLPRegressor(hidden=(8,)), optimizer="adam", loss="mse",
        feature_columns=["a", "b"], label_column="y", batch_size=64,
        num_epochs=1, seed=0, donate_state=False,
        checkpoint_dir=tempfile.mkdtemp(prefix="obs-serve-ckpt-"),
    )
    est.fit_on_etl(traced_session.from_pandas(pdf, num_partitions=2))
    x = pdf[["a", "b"]].to_numpy("float32")
    dep = serve.deploy(
        est, replicas=1, example=x[0],
        conf={"serve.max_batch_size": 8, "obs.request_sample_rate": 1.0},
    )
    yield dep, x
    dep.close()


def test_serve_request_trace_linkage(served_model):
    """Sampled request → batch fan-in → replica compute, one trace id:
    serve.request roots with queue_wait/batch_form/dispatch/respond
    children, ONE serve.batch span parented under a request and linking
    every sampled request id, and the replica's serve.replica_infer span
    landing under the batch's context from another process."""
    import time

    from raydp_tpu.cluster import api as cluster

    dep, x = served_model
    for i in range(4):
        dep.predict(x[i : i + 1])
    time.sleep(0.7)
    dep.predict(x[0:1])  # ships the replica's throttled span buffer
    time.sleep(0.2)
    obs.flush()
    spans = cluster.head_rpc("obs_dump")["spans"]
    requests = [s for s in spans if s["name"] == "serve.request"]
    batches = [s for s in spans if s["name"] == "serve.batch"]
    infers = [s for s in spans if s["name"] == "serve.replica_infer"]
    assert len(requests) >= 4 and batches and infers
    request_ids = {r["id"] for r in requests}
    assert any(b["parent"] in request_ids for b in batches)
    for b in batches:
        # the fan-in contract: every id a batch links IS a request span
        assert b["args"]["request_spans"], b["args"]
        assert set(b["args"]["request_spans"]) <= request_ids, b["args"]
    batch_ids = {b["id"] for b in batches}
    assert any(i["parent"] in batch_ids for i in infers), (
        "replica compute span not linked under a batch span"
    )
    # the replica span really is from another process
    linked = next(i for i in infers if i["parent"] in batch_ids)
    assert linked["proc"].startswith("worker:")
    # stage children cover the request's interior
    for name in ("serve.queue_wait", "serve.batch_form", "serve.dispatch",
                 "serve.respond"):
        children = [s for s in spans if s["name"] == name]
        assert children, name
        assert any(c["parent"] in request_ids for c in children), name
    # per-stage latency decomposition rides stats()
    stages = dep.stats()["stage_latency"]
    assert {"queue_wait", "batch_form", "dispatch", "compute",
            "respond"} <= set(stages)
    for entry in stages.values():
        assert entry["count"] >= 1 and entry["mean_ms"] >= 0.0


def test_scrape_carries_serve_series(served_model):
    """One real scrape of the head's endpoint with a deployment up parses
    and carries at least one ``serve_`` series beside a ``tenant``-labeled
    one: what a dashboard of a serving cluster reads."""
    from raydp_tpu.cluster import api as cluster
    from raydp_tpu.obs.timeseries import parse_prometheus_text, scrape

    dep, x = served_model
    dep.predict(x[0:1])
    host, port = cluster.head_rpc("obs_configure", scrape_port=0)["scrape_addr"]
    obs.flush()
    parsed = parse_prometheus_text(scrape(host, port))
    assert any(name.startswith("raydp_serve_") for name in parsed), sorted(parsed)
    assert any(
        key == "tenant"
        for series in parsed.values() for labels in series for key, _ in labels
    )


def test_serve_request_trace_sampling_off(served_model):
    """Unsampled arm: with shipping disabled no serve.request spans are
    minted (the sampler gates on tracing), while the stage histograms —
    always on — keep counting."""
    dep, x = served_model
    before_stats = dep.stats()["stage_latency"]["queue_wait"]["count"]
    tracing.set_enabled(False)
    try:
        dep.predict(x[0:1])
        dep.predict(x[1:2])
    finally:
        tracing.set_enabled(True)
    from raydp_tpu.obs.tracing import drain_local

    local = drain_local()
    assert not [s for s in local if s["name"] == "serve.request"]
    assert dep.stats()["stage_latency"]["queue_wait"]["count"] >= before_stats + 2


# ---------------------------------------------------------------------------
# flight recorder + crash dossiers
# ---------------------------------------------------------------------------


def test_flight_recorder_rings_unit():
    from raydp_tpu.obs.recorder import METRICS_TAIL_S, FlightRecorder

    rec = FlightRecorder()
    for tick in range(30):
        rec.note_ingest(
            "worker:a:1", "worker:a",
            spans=[{"name": f"s{tick}", "id": f"i{tick}"}],
            snapshot={"c": {"type": "counter", "value": float(tick)}},
            logs=[{"message": f"m{tick}"}],
            ts=1000.0 + tick,
        )
    snap = rec._snapshot_proc("worker:a:1")
    assert len(snap["spans"]) == 30
    # the metrics tail is pruned to the trailing window
    oldest = snap["metrics_tail"][0]["ts"]
    assert 1029.0 - oldest <= METRICS_TAIL_S
    dossier = rec.assemble("unit", victim_keys=["worker:a:1"],
                           victim={"actor_id": "a"},
                           head_state={"actors": []})
    assert dossier["victim_rings"][0]["proc"] == "worker:a:1"
    assert dossier["victim_rings"][0]["spans"][-1]["name"] == "s29"
    assert dossier["reason"] == "unit"


def test_crash_dossier_on_sigkill(traced_session):
    """Acceptance: a SIGKILLed executor produces a crash dossier on the
    head containing the victim's pre-death spans (they shipped with its
    final unthrottled dispatch flush), the actor table, and per-tenant
    accounting. Uses its OWN tenant session so the shared traced cluster
    keeps its executors."""
    import glob
    import time

    from raydp_tpu.cluster import api as cluster

    session = raydp_tpu.init_etl(
        "obs-dossier", num_executors=2, executor_cores=1,
        executor_memory="300M",
        configs={"etl.actor.env.RAYDP_TPU_TRACE": "1"},
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
        assert found["reason"] == "actor_killed"
        rings = found["victim_rings"]
        assert rings, "victim rings missing"
        assert any(victim_id in ring["proc"] for ring in rings)
        victim_spans = [
            s["name"] for ring in rings if victim_id in ring["proc"]
            for s in ring["spans"]
        ]
        # the victim's pre-death task spans shipped with its last dispatch
        assert "executor.task" in victim_spans, victim_spans
        # head context rides along: actor table + per-tenant accounting
        assert any(
            a["actor_id"] == victim_id for a in found["head"]["actors"]
        )
        assert "tenants" in found["head"]
    finally:
        session.stop()


def test_structured_logger_format(capsys):
    from raydp_tpu.obs.logging import get_logger

    log = get_logger("testrole")
    log.error("something broke", code=7)
    err = capsys.readouterr().err
    assert "ERROR" in err
    assert "[testrole" in err
    assert "something broke" in err
    assert "code=7" in err
    try:
        raise ValueError("inner detail")
    except ValueError:
        log.exception("with traceback")
    err = capsys.readouterr().err
    assert "inner detail" in err and "Traceback" in err
