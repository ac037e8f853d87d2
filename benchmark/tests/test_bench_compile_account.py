"""The compile account's per-layer readers (PR 55): each of the nine entries
has its reader file and is reported by all nine cells; a reader gives None on
a registry without its counter (the parent commit's) and the counter's value
otherwise; what the benchmark had before is a prefix of what it has."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, layers  # noqa: E402

# entry -> (the counters it reads, unit, source, the end-to-end metric it moves)
ENTRIES = {
    "estimator.compile_trace_s": (
        ["estimator.compile.trace_seconds"], "s", "program_counter", "setup_s"),
    "estimator.compile_lower_s": (
        ["estimator.compile.lower_seconds"], "s", "program_counter", "setup_s"),
    "estimator.compile_backend_s": (
        ["estimator.compile.backend_seconds"], "s", "program_counter",
        "setup_s"),
    "estimator.compile_cache_load_s": (
        ["estimator.compile.cache_load_seconds"], "s", "program_counter",
        "setup_s"),
    "estimator.compile_rest_s": (
        ["estimator.compile.rest_seconds"], "s", "program_span", "setup_s"),
    "estimator.compile_miss_programs": (
        ["estimator.compile.programs", "estimator.compile.cache_hits"],
        "programs", "program_counter", "setup_s"),
    "estimator.compile_outside_s": (
        ["jax.compile.outside_seconds"], "s", "program_counter", "setup_s"),
    "estimator.compile_late_s": (
        ["estimator.compile.late_seconds"], "s", "program_counter",
        "fit_samples_per_s"),
    "estimator.fit_unaccounted_s": (
        ["estimator.fit.unaccounted_seconds"], "s", "program_span", "setup_s"),
}
# the five that split ``estimator.compile_s``. The tests below hold this PR's
# nine to be a SUBSET of every cell's readers: a later PR's may join them
PARTS = ["estimator.compile_trace_s", "estimator.compile_lower_s",
         "estimator.compile_backend_s", "estimator.compile_cache_load_s",
         "estimator.compile_rest_s"]


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _read(monkeypatch, name, snapshot):
    from raydp_tpu import obs

    monkeypatch.setattr(obs.metrics, "snapshot", lambda: snapshot)
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    return layers.read_metric(path, {})


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_entry_is_as_the_issue_lists_it_in_every_cell(name):
    bench = _bench()
    every_cell = [w["name"] for w in bench["workloads"]]
    assert len(every_cell) >= 9
    entry = next(m for m in bench["per_layer"] if m["name"] == name)
    _, unit, source, moves = ENTRIES[name]
    assert entry == {
        "name": name, "unit": unit, "better": "lower", "source": source,
        "layer": "estimator", "moves": moves,
        "workloads": entry["workloads"]}
    assert set(every_cell[:9]) <= set(entry["workloads"])
    assert os.path.isfile(os.path.join(
        ROOT, "benchmark", "layer_metrics", name + ".py"))


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_gives_none_on_an_empty_registry(monkeypatch, name):
    assert _read(monkeypatch, name, {}) is None
    # the lump it splits is not what it reads
    assert _read(monkeypatch, name, {
        "estimator.compile_seconds": {"type": "counter", "value": 9.0}}) is None


@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_reader_gives_the_counters_value(monkeypatch, name):
    counters = ENTRIES[name][0]
    snapshot = {counter: {"type": "counter", "value": 7.25 - 2 * i}
                for i, counter in enumerate(counters)}
    want = 7.25 if len(counters) == 1 else 7.25 - 5.25  # programs - hits
    assert _read(monkeypatch, name, snapshot) == pytest.approx(want)
    if len(counters) == 2:  # one of the two alone says nothing
        assert _read(monkeypatch, name, {
            counters[0]: snapshot[counters[0]]}) is None


@pytest.mark.parametrize("cell_name", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves_with_the_readers(cell_name):
    cell = cells.resolve(ROOT, cell_name)
    names = {m["name"] for m in cell.per_layer}
    assert set(ENTRIES) <= names
    assert "estimator.compile_s" in names  # the lump stays beside its parts
    for name in ENTRIES:
        assert cell.layer_files[name].endswith(name + ".py")
    # every part reports the end-to-end metric the cell reports
    assert {"setup_s", "fit_samples_per_s"} <= {
        m["name"] for m in cell.end_to_end}


def test_the_parts_read_what_sums_to_the_lump():
    """On the program's own registry around one toy compile site: the five
    parts' readers move by what ``estimator.compile_s`` moves by."""
    from raydp_tpu import obs
    from raydp_tpu.obs import profiler

    def read_all():
        return {name: layers.read_metric(os.path.join(
            ROOT, "benchmark", "layer_metrics", name + ".py"), {}) or 0.0
            for name in PARTS + ["estimator.compile_s"]}

    was = read_all()
    site = profiler.open_compile_site("bench_test", fit=0)
    site["trace_s"], site["backend_s"] = 0.25, 1.5
    profiler.close_compile_site(site)
    assert profiler.settle_compile_site(site, wall=2.0) == pytest.approx(0.25)
    obs.metrics.counter("estimator.compile_seconds").inc(2.0)
    now = read_all()
    assert sum(now[name] - was[name] for name in PARTS) == pytest.approx(
        now["estimator.compile_s"] - was["estimator.compile_s"])
    assert now["estimator.compile_s"] - was["estimator.compile_s"] == (
        pytest.approx(2.0))


def test_what_the_benchmark_had_is_a_prefix_of_what_it_has():
    """Against the parent commit where git has it: nothing that was there
    moved or changed, the nine are the last entries of ``per_layer`` and no
    other key of the file differs."""
    bench = _bench()
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index("estimator.compile_trace_s")
    assert names[first:first + 9] == list(ENTRIES)
    done = subprocess.run(
        ["git", "show", "HEAD:BENCHMARK.json"], cwd=ROOT,
        capture_output=True, text=True)
    if done.returncode != 0:
        pytest.skip("no git history here: the order alone was checked")
    had = json.loads(done.stdout)
    known = {m["name"] for m in had["per_layer"]}
    if set(ENTRIES) <= known:
        pytest.skip("HEAD already holds this PR's entries")
    assert bench["per_layer"][:len(had["per_layer"])] == had["per_layer"]
    assert {k: v for k, v in bench.items() if k != "per_layer"} == {
        k: v for k, v in had.items() if k != "per_layer"}
