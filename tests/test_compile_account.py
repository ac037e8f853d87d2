"""The compile account (ISSUE 55): what ``jax.monitoring`` reports of every
compile in the process, kept by the ``estimator.compile`` site open on the
reporting thread, by name where none is, and once more where it comes after a
fit's first fence (obs/profiler.py, docs/observability.md "Compile account").

On the CPU backend, against a persistent cache of this file's own (a
temporary directory, both thresholds at 0: every program is written and found
again). Times here say nothing about a chip; the tests hold sums, counts and
names."""

import threading
import time

import numpy as np
import pytest

from raydp_tpu import compile_cache, obs
from raydp_tpu.estimator import JaxEstimator
from raydp_tpu.obs import profiler

_DIMS = (8, 24, 1)
_PARTS = ("trace_s", "lower_s", "backend_s", "cache_load_s")


@pytest.fixture(autouse=True, scope="module")
def own_cache(tmp_path_factory):
    """A cache directory no earlier test wrote to: a first compile misses."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {name: getattr(jax.config, name) for name in names}
    jax.config.update(names[0], str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update(names[1], True)
    jax.config.update(names[2], 0.0)
    jax.config.update(names[3], -1)
    compilation_cache.reset_cache()
    compile_cache.enable_compile_cache()  # registers the listeners
    # the table of names is bounded: what earlier tests of this worker
    # compiled must not crowd this file's out
    profiler._outside.clear()
    yield
    for name, value in before.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()


def _mlp():
    import flax.linen as nn

    class AccountMLP(nn.Module):
        @nn.compact
        def __call__(self, x):
            return nn.Dense(_DIMS[2])(nn.tanh(nn.Dense(_DIMS[1])(x)))

    return AccountMLP()


class _HostDs:
    def __init__(self, rows=1024, seed=11):
        rng = np.random.default_rng(seed)
        self._f = rng.random((rows, _DIMS[0])).astype(np.float32)
        self._l = (self._f @ rng.random(_DIMS[0])).astype(np.float32)
        self.uuid, self.blocks = f"compile-account-{seed}", []

    def to_numpy(self, feature_columns, label_column, feature_dtype,
                 label_dtype):
        return self._f.astype(feature_dtype), self._l.astype(label_dtype)


def _est(**overrides):
    import jax
    from jax.sharding import Mesh

    kwargs = dict(
        model=_mlp, optimizer="sgd", loss="mse",
        feature_columns=[f"f{i}" for i in range(_DIMS[0])],
        label_column="y", batch_size=64, num_epochs=2, seed=7,
        mesh=Mesh(np.array(jax.devices()[:1]), ("data",)),
    )
    kwargs.update(overrides)
    return JaxEstimator(**kwargs)


def _counter(name):
    return obs.metrics.counter(name).value


@pytest.fixture(scope="module")
def two_fits():
    """The same toy fit by two estimators of one process: the first compiles
    anew, the second finds every program in the cache."""
    first, second = _est(), _est()
    first.fit(_HostDs(), _HostDs(rows=256, seed=12))
    second.fit(_HostDs(), _HostDs(rows=256, seed=12))
    return first, second


def test_first_estimator_misses_and_second_hits(two_fits):
    first, second = two_fits
    cold, warm = first.compile_account(), second.compile_account()
    whats = [site["what"] for site in cold["sites"]]
    assert whats == [site["what"] for site in warm["sites"]]
    assert {"init", "flops_probe"} <= set(whats)
    for site in cold["sites"]:
        assert site["programs"] == 1, site
        assert (site["cache_hits"], site["cache_misses"]) == (0, 1), site
        assert site["backend_s"] > 0.0 and site["cache_load_s"] == 0.0, site
    for site in warm["sites"]:
        assert site["programs"] == 1, site
        assert (site["cache_hits"], site["cache_misses"]) == (1, 0), site
        assert site["cache_load_s"] > 0.0 and site["backend_s"] == 0.0, site
    assert cold["totals"]["cache_misses"] == len(whats)
    assert warm["totals"]["cache_hits"] == len(whats)


def test_every_site_sums_to_its_wall(two_fits):
    for est in two_fits:
        records = [r for r in est.last_fit_records_
                   if r["name"] == "estimator.compile"]
        assert len(records) >= 3
        for record in records:
            args = record["args"]
            assert args["rest_s"] >= 0.0
            total = sum(args[part] for part in _PARTS) + args["rest_s"]
            assert total == pytest.approx(record["dur"] / 1e6, abs=2e-6)
            assert args["trace_s"] > 0.0 and args["lower_s"] > 0.0


def test_fit_stats_hold_the_fits_own_account(two_fits):
    first, second = two_fits
    for est in two_fits:
        said = est.fit_stats_["compile"]
        assert {site["fit"] for site in said["sites"]} == {est._fit_seq}
        wall = sum(site["wall_s"] for site in said["sites"])
        assert wall == pytest.approx(est.compile_seconds_)
        assert said["totals"]["wall_s"] == pytest.approx(wall)
    assert first._fit_seq != second._fit_seq


def test_counters_carry_the_split(two_fits):
    """Process totals, like ``estimator.compile_seconds``, which stays the
    sum of the five."""
    snap = obs.metrics.snapshot()
    parts = sum(snap[f"estimator.compile.{name}_seconds"]["value"]
                for name in ("trace", "lower", "backend", "cache_load", "rest"))
    assert parts == pytest.approx(
        snap["estimator.compile_seconds"]["value"], rel=1e-6)
    assert (snap["estimator.compile.programs"]["value"]
            >= snap["estimator.compile.cache_hits"]["value"] >= 3)
    assert snap["estimator.compile.cache_misses"]["value"] >= 3


def test_nested_jits_are_not_counted_twice():
    """An inner ``jit``'s trace event lies inside the outer's: the site's
    trace seconds are the union of the intervals, not their sum."""
    import jax
    import jax.numpy as jnp

    durations = []

    def note(event, duration, **kwargs):
        if event.endswith("jaxpr_trace_duration"):
            durations.append(duration)

    @jax.jit
    def inner(x):
        time.sleep(0.05)  # at trace time
        return jnp.sin(x) * 2.0

    @jax.jit
    def outer(x):
        return inner(x) + inner(x * 3.0) + 1.0

    x = jnp.ones((5,))
    jax.monitoring.register_event_duration_secs_listener(note)
    try:
        site = profiler.open_compile_site("nested", fit=0)
        t0 = time.perf_counter()
        outer.lower(x).compile()
        wall = time.perf_counter() - t0
        said = profiler.close_compile_site(site)
    finally:
        jax.monitoring.unregister_event_duration_listener(note)
    # inner's 50 ms are in its own event and in outer's
    assert sum(durations) > said["trace_s"] + 0.04
    assert 0.05 <= said["trace_s"] <= wall
    assert sum(said[part] for part in _PARTS) <= wall
    assert said["programs"] == 1


def test_backend_compile_inside_a_trace_comes_off_the_trace():
    """An operation compiled while a function is traced is a program of its
    own: its backend seconds are not trace seconds too."""
    import jax
    import jax.numpy as jnp

    raw = []

    def note(event, duration, **kwargs):
        raw.append((event.rsplit("/", 1)[-1], kwargs.get("fun_name"), duration))

    @jax.jit
    def traced(x):
        with jax.ensure_compile_time_eval():  # concrete: compiles now
            eager = jnp.cumsum(jnp.arange(13.0)) * 0.37
        return x + eager

    x = jnp.ones((13,))
    jax.monitoring.register_event_duration_secs_listener(note)
    try:
        site = profiler.open_compile_site("eager_inside", fit=0)
        t0 = time.perf_counter()
        traced.lower(x).compile()
        wall = time.perf_counter() - t0
        said = profiler.close_compile_site(site)
    finally:
        jax.monitoring.unregister_event_duration_listener(note)
    assert said["programs"] >= 2
    held = sum(d for kind, name, d in raw
               if kind == "backend_compile_duration" and name != "jit(traced)")
    whole = max(d for kind, name, d in raw
                if kind == "jaxpr_trace_duration" and name == "traced")
    assert held > 0.0
    assert said["trace_s"] <= whole - held + 1e-3
    assert sum(said[part] for part in _PARTS) <= wall


def test_compile_outside_any_site_is_named(two_fits):
    """A compile on a thread with no site open goes to ``jax.compile.
    outside_*`` and to the table by name, under the innermost obs span."""
    import jax
    import jax.numpy as jnp

    def account_stranger(x):
        return jnp.tanh(x) * 1.25 + 0.5

    x = jnp.ones((3, 3))
    before = (_counter("jax.compile.outside_seconds"),
              _counter("jax.compile.outside_programs"))
    sites = len(profiler.compile_account()["sites"])
    seen = {}

    def work():
        with obs.collect():
            with obs.span("exchange.upload"):
                seen["name"] = obs.tracing.current_span_name()
                jax.jit(account_stranger)(x).block_until_ready()
            seen["after"] = obs.tracing.current_span_name()

    thread = threading.Thread(target=work)
    thread.start()
    thread.join()
    assert seen == {"name": "exchange.upload", "after": None}
    account = profiler.compile_account()
    row = account["outside"]["account_stranger"]
    assert row["programs"] == 1 and row["seconds"] > 0.0
    assert row["under"] == "exchange.upload"
    assert _counter("jax.compile.outside_programs") == before[1] + 1
    assert _counter("jax.compile.outside_seconds") >= before[0] + row["seconds"] - 1e-9
    assert len(account["sites"]) == sites
    # the same name under another span is a row of its own

    def again():
        def account_stranger(x):
            return jnp.tanh(x) * 2.5 - 0.5

        with obs.collect(), obs.span("estimator.eval"):
            jax.jit(account_stranger)(x).block_until_ready()

    thread = threading.Thread(target=again)
    thread.start()
    thread.join()
    outside = profiler.compile_account()["outside"]
    assert outside["account_stranger"]["programs"] == 1
    other = outside["account_stranger [estimator.eval]"]
    assert other["programs"] == 1 and other["under"] == "estimator.eval"
    # the evaluation's programs have no site: named under their span
    assert account["outside"]["eval_scan"]["under"] == "estimator.eval"


def test_outside_table_is_bounded():
    import jax
    import jax.numpy as jnp

    for i in range(profiler.NAMED_ROWS_SHOWN + 4):
        fn = (lambda k: lambda x: x * float(k + 2))(i)
        fn.__name__ = f"bounded_{i}"
        jax.jit(fn)(jnp.ones(())).block_until_ready()
    outside = profiler.compile_account()["outside"]
    assert len(outside) <= profiler.NAMED_ROWS_SHOWN + 1
    assert "other" in outside and outside["other"]["programs"] >= 1


def _segment_est(num_epochs=3):
    return _est(scan_memory_limit=1, stream_scan_steps=8,
                num_epochs=num_epochs)


def test_steady_fit_compiles_nothing_late():
    before = (_counter("estimator.compile.late_programs"),
              _counter("estimator.compile.late_seconds"))
    est = _segment_est()
    est.fit(_HostDs())
    assert est.fit_stats_["runner"] == "segment_scan"
    assert est.compile_account()["late"] == []
    assert _counter("estimator.compile.late_programs") == before[0]
    assert _counter("estimator.compile.late_seconds") == before[1]


def test_new_segment_length_after_the_first_fence_is_late():
    """From its second epoch on the producer hands over segments of half the
    length: a program compiled after the first fence, counted and named."""
    before = _counter("estimator.compile.late_programs")
    est = _segment_est()
    batches, calls = est._epoch_batches, []

    def halved(source, batch_size, seed, shuffle=None, segment_rows=None):
        calls.append(segment_rows)
        if len(calls) > 1 and segment_rows:
            segment_rows //= 2
        return batches(source, batch_size, seed, shuffle, segment_rows)

    est._epoch_batches = halved
    history = est.fit(_HostDs())
    assert len(history) == 3
    account = est.compile_account()
    assert [s["what"] for s in account["sites"]
            if s["what"].isdigit()] == ["8", "4"]
    assert _counter("estimator.compile.late_programs") >= before + 1
    named = {row["fun_name"]: row for row in account["late"]}
    assert "epoch_body" in named, named
    row = named["epoch_body"]
    assert row["programs"] == 1 and row["seconds"] > 0.0
    assert row["under"] == "4" and row["epoch"] == 1
    assert row["fit"] == est._fit_seq
    assert account["totals"]["late_programs"] >= 1


def test_enabling_the_cache_twice_counts_an_event_once():
    import jax

    compile_cache.enable_compile_cache()
    compile_cache.enable_compile_cache()
    from jax._src import monitoring

    assert monitoring.get_event_listeners().count(
        profiler._on_compile_event) == 1
    assert monitoring.get_event_duration_listeners().count(
        profiler._on_compile_duration) == 1
    site = profiler.open_compile_site("once", fit=0)
    jax.jit(lambda x: x - 41.5).lower(np.float32(1.0)).compile()
    said = profiler.close_compile_site(site)
    assert said["programs"] == 1
    assert said["cache_hits"] + said["cache_misses"] == 1


def test_account_answers_from_another_thread_mid_fit():
    est = _segment_est(num_epochs=80)
    failure, seen = [], []

    def job():
        try:
            est.fit(_HostDs())
        except BaseException as exc:  # noqa: BLE001 - reported below
            failure.append(exc)

    thread = threading.Thread(target=job, daemon=True)
    thread.start()
    deadline = time.time() + 120
    while time.time() < deadline and thread.is_alive() and not failure:
        if len(est.history) >= 2:
            seen.append(est.compile_account())
            break
        time.sleep(0.005)
    alive = thread.is_alive()
    assert not failure, failure
    assert seen and alive, "the fit ended before it was asked"
    sites = seen[0]["sites"]
    assert {"init", "8", "flops_probe"} <= {s["what"] for s in sites}
    assert all(s["wall_s"] is not None and s["fit"] == est._fit_seq
               for s in sites)
    thread.join(timeout=120)


def test_fit_residue_is_inside_the_fits_wall():
    before = (_counter("estimator.fit.first_fence_seconds"),
              _counter("estimator.fit.unaccounted_seconds"))
    est = _est(num_epochs=2)
    t0 = time.perf_counter()
    est.fit(_HostDs(), _HostDs(rows=256, seed=12))
    wall = time.perf_counter() - t0
    first_fence = _counter("estimator.fit.first_fence_seconds") - before[0]
    unaccounted = _counter("estimator.fit.unaccounted_seconds") - before[1]
    assert 0.0 < first_fence < wall
    assert 0.0 <= unaccounted < first_fence
    # the fit's children closed before the fence are what was taken off
    children = {"exchange.stage", "estimator.compile",
                "estimator.row_update_probe", "estimator.epoch",
                "estimator.eval"}
    names = {r["name"] for r in est.last_fit_records_}
    assert children <= names


def test_span_name_rides_no_noop_span():
    assert obs.tracing.current_span_name() is None
    with obs.span("account.noop"):  # no collector, no shipping: the no-op
        assert obs.tracing.current_span_name() is None
    with obs.collect():
        with obs.span("account.outer"):
            with obs.span("account.inner"):
                assert obs.tracing.current_span_name() == "account.inner"
            assert obs.tracing.current_span_name() == "account.outer"
    assert obs.tracing.current_span_name() is None
