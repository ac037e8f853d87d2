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
def segment_fit(host_ds):
    """One real 2-epoch fit on the segment runner (staged data over the
    limit: four 8-step segments an epoch), shared by the phase/attribution/
    MFU tests. The runner fences every second dispatch here, so the
    ``sync`` phase has something to show."""
    from raydp_tpu.estimator import jax_estimator

    est = _make_est(scan_memory_limit=1, stream_scan_steps=8)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax_estimator, "MAX_DISPATCHES_IN_FLIGHT", 2)
        history = est.fit(host_ds)
    return est, history


def test_step_phase_histograms_present_and_sane(segment_fit):
    est, history = segment_fit
    assert len(history) == 2
    stats = est.fit_stats_
    assert stats["runner"] == "segment_scan"
    assert stats["steps"] == 2 * (2048 // 64)
    phases = stats["step_phase_seconds"]
    assert set(phases) == {"ingest", "h2d", "dispatch", "sync"}
    assert all(seconds > 0.0 for seconds in phases.values()), phases
    # what the consumer thread notes (its wait for a segment, its time in
    # the compiled call, its fences) lies inside the epochs' spans; the
    # uploads (h2d) are the producer thread's and overlap them
    consumer = phases["ingest"] + phases["dispatch"] + phases["sync"]
    assert consumer <= 1.1 * sum(r["epoch_seconds"] for r in history)
    # the registry carries the phase histograms (scrapeable mid-fit): an
    # observation a segment, none a step
    snap = obs.metrics.snapshot()
    assert "estimator.step.compute_ms" not in snap
    for phase in ("ingest", "h2d", "dispatch", "sync"):
        hist = snap[f"estimator.step.{phase}_ms"]
        assert hist["type"] == "histogram" and hist["count"] > 0
        assert hist["max"] >= hist["p50"] >= 0.0


def test_explain_last_fit_attribution(segment_fit):
    est, _history = segment_fit
    report = est.explain_last_fit()
    assert report["root"] == "estimator.fit"
    # acceptance gate: ≥0.9 of the fit's wall time lands in NAMED segments
    assert report["attributed_frac"] >= 0.9, report["text"]
    # the epoch's own spans surface the compute-plane categories: the
    # host's time inside the compiled calls and the compiles; a fence has
    # no span and stays the epoch's own time
    assert report["by_category"].get("dispatch", 0.0) > 0.0
    assert "compile" in report["by_category"]
    assert report["text"].startswith("critical path of estimator.fit")


def test_live_mfu_vs_analytic_parity(segment_fit):
    est, _history = segment_fit
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
    wall = stats["flops_per_step"] * stats["steps_completed"] / (
        stats["model_flops_per_sec"]
    )
    assert wall > stats["step_phase_seconds"]["dispatch"]


def test_scan_path_reports_same_flops(host_ds, segment_fit):
    """Both runners report the SAME FLOPs-per-step (one accounting): a
    scan executable is opaque to cost analysis, so the single-step abstract
    lowering covers both."""
    est_scan = _make_est()  # under the default limit: the resident scan
    est_scan.fit(host_ds)
    assert est_scan.fit_stats_["runner"] == "resident_scan"
    segment_est, _ = segment_fit
    assert est_scan.fit_stats_["flops_per_step"] == pytest.approx(
        segment_est.fit_stats_["flops_per_step"]
    )
    assert est_scan.fit_stats_["steps"] == 2 * (2048 // 64)


def test_mfu_series_reaches_local_mirror(segment_fit):
    """The estimator.mfu gauge rides the flush tick into the windowed
    time-series mirror — what a head scrape would show."""
    obs.flush()
    series = obs.query_local_series("estimator.mfu", window_s=600.0)
    assert series, "estimator.mfu series missing from the local mirror"
    assert series[-1]["last"] > 0.0


def test_step_profiler_off_is_noop(host_ds):
    profiler.set_step_profiler(False)
    try:
        est = _make_est(
            scan_memory_limit=1, stream_scan_steps=8, num_epochs=1)
        est.fit(host_ds)
        assert est.fit_stats_["profiler"] == "off"
        assert est.fit_stats_["step_phase_seconds"] == {}
    finally:
        profiler.set_step_profiler(True)


# ---------------------------------------------------------------------------
# completed steps, the epoch restart, stable device names (ISSUE 24)
# ---------------------------------------------------------------------------

_RUNNERS = {
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
    est = _make_est(num_epochs=3, scan_memory_limit=1, stream_scan_steps=8)
    before = hist.count
    est.fit(host_ds)
    assert hist.count == before


def test_compile_and_stage_counters_readable(host_ds):
    compile_c = obs.metrics.counter("estimator.compile_seconds")
    stage_c = obs.metrics.counter("exchange.stage_seconds")
    # the compile account's split of the same seconds (ISSUE 55)
    split = [obs.metrics.counter(f"estimator.compile.{part}_seconds")
             for part in ("trace", "lower", "backend", "cache_load", "rest")]
    before = compile_c.value, stage_c.value, sum(c.value for c in split)
    est = _make_est(num_epochs=1)
    est._stage_cache = {}
    est.fit(_HostDs(host_ds._f.copy(), host_ds._l.copy()))
    assert compile_c.value - before[0] == pytest.approx(est.compile_seconds_)
    assert sum(c.value for c in split) - before[2] == pytest.approx(
        est.compile_seconds_)
    assert all(c.value >= 0.0 for c in split)
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
    est = _make_est(scan_memory_limit=1, stream_scan_steps=8, num_epochs=1)
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
# device scopes: what each instruction of a compiled program belongs to
# ---------------------------------------------------------------------------


@pytest.fixture()
def fresh_scopes(monkeypatch):
    """A registry and a programs' table of this test's own."""
    monkeypatch.setattr(profiler, "_scope_names", {})
    monkeypatch.setattr(profiler, "_programs", {})
    return profiler


def _scoped_toy():
    """A step with nested scopes under grad(checkpoint(..)) inside a scan,
    an optimizer half, and an instruction outside every scope."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def step(w, x):
        def loss(w):
            def body(h, _):
                with obs.device_scope("toy.block"):
                    with obs.device_scope("toy.block.mix"):
                        h = jnp.tanh(h @ w)
                    with obs.device_scope("toy.block.act"):
                        h = h * 2.0 + 1.0
                return h, None

            h, _ = lax.scan(jax.checkpoint(body), x, None, length=3)
            with obs.device_scope("toy.loss"):
                return (h ** 2).sum()

        with obs.device_scope("toy.grad"):
            value, grad = jax.value_and_grad(loss)(w)
        with obs.device_scope("toy.update"):
            w = w - 0.1 * grad
        return w, value, jnp.flip(x, 0)  # the last: outside every scope

    w, x = jnp.ones((64, 64)), jnp.ones((8, 64))
    return jax.jit(step).lower(w, x).compile()


def _op_names(text):
    import re

    return {m.group(1): m.group(2) for m in re.finditer(
        r"^\s+(?:ROOT\s+)?%?([^\s=]+) = .*op_name=\"([^\"]*)\"", text, re.M)}


def test_device_scopes_follow_grad_checkpoint_and_scan(fresh_scopes):
    compiled = _scoped_toy()
    assert profiler.registered_scopes() == (
        "toy.grad", "toy.block", "toy.block.mix", "toy.block.act",
        "toy.loss", "toy.update")
    profiler.note_program("toy", compiled)
    (key, said), = profiler.device_scopes().items()
    assert key.startswith("toy#")
    names = _op_names(compiled.as_text())
    mix = ["toy.grad", "toy.block", "toy.block.mix"]
    products = {"forward": [], "recomputed": [], "backward": []}
    for name, op_name in names.items():
        if name in said and "toy.block.mix" in op_name and "dot" in name:
            kind = ("recomputed" if "rematted_computation" in op_name else
                    "backward" if "transpose(" in op_name else "forward")
            products[kind].append(name)
    for kind, found in products.items():
        assert found, (kind, "no product of that pass in the map")
        for name in found:
            assert said[name]["scopes"] == mix, (kind, name)
            assert said[name]["result"] in ("f32[8,64]", "f32[64,64]")
    # every one of them lies in a loop's body: called computations are walked
    text = compiled.as_text()
    entry = text[text.index("ENTRY"):]
    assert not any(f"%{name} = " in entry for name in products["forward"])
    # one elementwise fusion holds the activation and the product's tanh
    mixed = [v for v in said.values() if v.get("mixed")]
    assert mixed and any(v["scopes"][-1] == "toy.block.act" for v in mixed)
    # the half outside the gradient, and the instruction outside everything
    assert any(v["scopes"] == ["toy.update"] for v in said.values())
    flips = [name for name, op_name in names.items()
             if name in said and op_name.endswith("/rev")]
    assert flips and all(said[name]["scopes"] == [] for name in flips)
    # parameters and compiler-made instructions carry no op_name: no scope
    assert all(v["scopes"] == [] for name, v in said.items()
               if name not in names)
    # asked again: the text is not read again
    reads = []
    compiled.as_text = lambda: reads.append(1)  # noqa: E731
    assert profiler.device_scopes()[key] is said and not reads


def test_a_collected_program_is_left_out_and_nothing_raises(fresh_scopes):
    import gc

    class Unprintable:
        def as_text(self):
            raise RuntimeError("no text on this backend")

    kept, broken = _scoped_toy(), Unprintable()
    profiler.note_program("kept", kept)
    profiler.note_program("gone", _scoped_toy())
    profiler.note_program("broken", broken)
    gc.collect()
    assert [k.partition("#")[0] for k in profiler.device_scopes()] == ["kept"]
    del kept
    gc.collect()
    assert profiler.device_scopes() == {} and not profiler._programs.keys() - {
        entry.seq for entry in profiler._programs.values()
        if entry.what == "broken"}


def test_scope_chain_reads_names_inside_transforms():
    names = {"loss_and_grad", "hybridlm.experts", "hybridlm.experts.gmm",
             "ssd", "dot_general"}
    assert profiler.scope_chain(
        "jit(f)/loss_and_grad/transpose(jvp())/checkpoint/"
        "rematted_computation/hybridlm.experts/hybridlm.experts.gmm/"
        "dot_general", names) == [
            "loss_and_grad", "hybridlm.experts", "hybridlm.experts.gmm"]
    assert profiler.scope_chain(
        "jit(f)/loss_and_grad/transpose(jvp(ssd))/mul", names) == [
            "loss_and_grad", "ssd"]
    # the primitive's own name at the end is not a scope, whatever it is
    assert profiler.scope_chain("jit(f)/while/body/ssd", names) == []
    assert profiler.scope_chain("gather", names) == []


_FUSED_UPDATE = """
%fused_computation.1 (p.0: f32[8,8], p.1: bf16[4,8], p.2: bf16[4,8]) -> (f32[8,8], f32[8,8]) {
  %p.0 = f32[8,8]{1,0} parameter(0)
  %p.1 = bf16[4,8]{1,0} parameter(1)
  %p.2 = bf16[4,8]{1,0} parameter(2)
  %convolution.3 = f32[8,8]{1,0} convolution(%p.1, %p.2), dim_labels=fb_io->bf, metadata={op_name="jit(f)/loss_and_grad/transpose(jvp(m.mlp))/dot_general"}
  %mul.4 = f32[8,8]{1,0} multiply(%convolution.3, %p.0), metadata={op_name="jit(f)/optimizer_update/mul"}
  %add.5 = f32[8,8]{1,0} add(%mul.4, %p.0), metadata={op_name="jit(f)/optimizer_update/add"}
  ROOT %tuple.6 = (f32[8,8]{1,0}, f32[8,8]{1,0}) tuple(%add.5, %mul.4)
}

ENTRY %main.9 (a: f32[8,8], b: bf16[4,8], c: bf16[4,8]) -> (f32[8,8], f32[8,8]) {
  %a = f32[8,8]{1,0} parameter(0)
  %b = bf16[4,8]{1,0} parameter(1)
  %c = bf16[4,8]{1,0} parameter(2)
  ROOT %multiply_add_fusion.7 = (f32[8,8]{1,0}, /*index=1*/f32[8,8]{1,0}) fusion(%a, %b, %c), kind=kOutput, calls=%fused_computation.1METADATA
}
"""


@pytest.mark.parametrize("own, want", [
    # XLA names a fusion around a product by the product: the event is
    # mostly the product's time, and it stays with the layer
    ('jit(f)/loss_and_grad/transpose(jvp(m.mlp))/dot_general',
     ["loss_and_grad", "m.mlp"]),
    # a name without a scope, a root of the compiler's own (the tuple): the
    # last fused instruction's that has any
    ("jit(f)/while/body", ["optimizer_update"]),
    (None, ["optimizer_update"]),
])
def test_a_fusion_belongs_to_what_xla_names_it_by(own, want):
    names = {"loss_and_grad", "optimizer_update", "m.mlp"}
    metadata = f', metadata={{op_name="{own}"}}' if own else ""
    said = profiler.scopes_in_text(
        _FUSED_UPDATE.replace("METADATA", metadata), names)
    assert said["multiply_add_fusion.7"] == {
        "result": "(f32[8,8], /*index=1*/f32[8,8])", "scopes": want,
        "mixed": True}
    assert "convolution.3" not in said  # a fusion's insides are no events


def test_capture_window_writes_device_scopes(host_ds, tmp_path, fresh_scopes):
    est = _make_est(num_epochs=1)
    with profiler.profile_fit(steps=8, out_dir=str(tmp_path / "cap"),
                              jax_trace=False) as cap:
        est.fit(host_ds, host_ds)
    result = cap.result()
    assert os.path.basename(result["device_scopes_path"]) == "device_scopes.json"
    with open(result["device_scopes_path"]) as f:
        programs = json.load(f)
    # the fit has returned and dropped its programs: the window pinned them
    whats = {key.partition("#")[0] for key in programs}
    assert {"init", "32", "eval_scan"} <= whats, whats
    step = next(v for k, v in programs.items() if k.startswith("32#"))
    chains = {tuple(said["scopes"]) for said in step.values()}
    assert {("loss_and_grad",), ("optimizer_update",)} <= chains
    assert cap.pinned == []
    import gc

    gc.collect()
    assert profiler.device_scopes() == {}


@pytest.mark.parametrize("runner", ["scan", "segments"])
def test_a_fit_nobody_profiles_reads_no_program_text(
        host_ds, monkeypatch, fresh_scopes, runner):
    import jax

    reads = []
    real = jax.stages.Compiled.as_text
    monkeypatch.setattr(
        jax.stages.Compiled, "as_text",
        lambda self, *a, **k: reads.append(1) or real(self, *a, **k))
    monkeypatch.setattr(
        costmodel, "step_flops_abstract", lambda *a, **k: 1.0)
    asked = []
    monkeypatch.setattr(profiler, "scopes_in_text",
                        lambda *a, **k: asked.append(1) or {})
    est = _make_est(num_epochs=2, **(
        {"scan_memory_limit": 1, "stream_scan_steps": 8}
        if runner == "segments" else {}))
    noted = []
    real_note = profiler.note_program
    monkeypatch.setattr(
        profiler, "note_program",
        lambda what, program: noted.append(str(what)) or real_note(what, program))
    est.fit(host_ds, host_ds)
    assert not reads and not asked
    # one note a compiled program, none an epoch
    step = "8" if runner == "segments" else "32"
    evaluation = "eval_step" if runner == "segments" else "eval_scan"
    assert sorted(noted) == sorted(["init", step, evaluation]), noted


def _toy_model(kind):
    import jax
    import jax.numpy as jnp

    from raydp_tpu.estimator import jax_estimator as je

    rng = np.random.default_rng(0)
    if kind == "dlrm":
        from raydp_tpu.models import DLRM

        module = DLRM(vocab_sizes=(11, 7), num_dense=3, embed_dim=4,
                      bottom_mlp=(8, 4), top_mlp=(8, 1),
                      use_pallas_interaction=False)
        x = (jnp.asarray(rng.random((16, 3)), jnp.float32),
             jnp.asarray(rng.integers(0, 7, (16, 2)), jnp.int32))
        y = jnp.asarray(rng.random(16) > 0.5, jnp.float32)
        return module, je._LOSSES["bce"], x, y, {"dlrm_interaction"}
    tokens = jnp.asarray(rng.integers(0, 256, (2, 33)), jnp.int32)
    if kind == "looplm":
        from raydp_tpu.models import LoopLM

        module = LoopLM(vocab_size=256, hidden_size=64, num_heads=4,
                        num_layers=2, intermediate_size=176, loop_steps=2,
                        dtype=jnp.float32, loss_chunk=16)
        return module, je.MODEL_LOSS, tokens, None, {
            "looplm.loop", "looplm.block", "looplm.exit_loss"}
    from raydp_tpu.models import HybridLM, RoutedHybridLM

    if kind == "hybridlm-mamba":
        config = {
            "vocab_size": 256, "hidden_size": 64, "num_attention_heads": 4,
            "num_key_value_heads": 2, "shared_intermediate_size": 96,
            "layer_types": ["mamba", "attention", "mamba", "mamba"],
            "num_hidden_layers": 2, "mamba_n_heads": 8, "mamba_d_head": 16,
            "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_chunk_size": 8,
            "mamba_expand": 2, "mamba_n_groups": 1, "embedding_multiplier": 12,
            "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
            "logits_scaling": 8, "rms_norm_eps": 1e-5}
        module = HybridLM.from_config(config, dtype=jnp.float32, loss_chunk=16)
        return module, je.MODEL_LOSS, tokens, None, {
            "hybridlm.mamba", "hybridlm.attention", "hybridlm.mlp",
            "hybridlm.loss", "ssd"}
    config = {
        "model_type": "lfm2_moe", "vocab_size": 256, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 96, "moe_intermediate_size": 32,
        "layer_types": ["conv", "conv", "full_attention", "conv"],
        "num_hidden_layers": 3, "num_dense_layers": 1,
        "num_experts": 2, "num_experts_per_tok": 2, "conv_L_cache": 3,
        "conv_bias": False, "norm_eps": 1e-5, "norm_topk_prob": True,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True,
        "share": {"first_layer": 1, "experts_total": 8, "first_expert": 2}}
    if kind == "hybridlm-dense":
        config = {**config, "num_hidden_layers": 1}
        del config["share"]
        want = {"hybridlm.conv", "hybridlm.mlp", "hybridlm.loss"}
    else:
        want = {"hybridlm.conv", "hybridlm.attention", "hybridlm.mlp",
                "hybridlm.loss", "hybridlm.experts",
                "hybridlm.experts.route", "hybridlm.experts.dispatch",
                "hybridlm.experts.gmm", "hybridlm.experts.combine"}
    module = RoutedHybridLM.from_config(
        config, dtype=jnp.float32, loss_chunk=16, expert_bias_spread=0.05)
    return module, je.MODEL_LOSS, tokens, None, want


@pytest.mark.parametrize("kind", [
    "dlrm", "looplm", "hybridlm-dense", "hybridlm-mamba", "hybridlm-experts"])
def test_every_scope_a_model_registers_is_in_its_compiled_step(
        kind, fresh_scopes):
    """The names a model's trace registers are the ones a reader finds in
    the compiled step: none lost to a transform, a checkpoint or a fusion."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.estimator import jax_estimator as je

    module, loss_fn, x, y, want = _toy_model(kind)
    key = jax.random.PRNGKey(0)
    params = (module.init(key, x, None, method="loss")
              if loss_fn == je.MODEL_LOSS else module.init(key, x))
    assert profiler.registered_scopes()  # init traced the model already
    tx = optax.adam(1e-3)
    step = je.make_train_step(module, loss_fn, tx)
    compiled = jax.jit(step).lower(
        params, tx.init(params), jnp.zeros(()), x, y).compile()
    registered = set(profiler.registered_scopes())
    assert registered == want | {"loss_and_grad", "optimizer_update"}
    said = profiler.scopes_in_text(compiled.as_text())
    found = {scope for v in said.values() for scope in v["scopes"]}
    assert found == registered, registered - found
    # the halves of a step do not nest, and the model runs in the first (an
    # instruction the compiler hoisted out of a loop may have lost the
    # outer half of its name: a causal mask reads ``looplm.block`` alone)
    for v in said.values():
        chain = v["scopes"]
        assert not {"loss_and_grad", "optimizer_update"} <= set(chain)
        assert not (chain[:1] == ["optimizer_update"] and chain[-1] in want)


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
