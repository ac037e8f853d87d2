"""The per-layer readers that read the program's own registry (PR 24):
each against a fake snapshot, with its instrument there and missing (as on a
commit from before the instrument), and both cells resolved with them."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, layers  # noqa: E402

COUNTER = {"type": "counter", "value": 7.25}
EMPTY_HIST = {"type": "histogram", "count": 0, "sum": 0.0}
HIST = {"type": "histogram", "count": 4, "sum": 10.0, "min": 1.0, "max": 4.0,
        "mean": 2.5, "p50": 2.0, "p99": 4.0}

# metric -> (snapshot with the instrument, what the reader gives, snapshots
# in which it has nothing to read)
CASES = {
    "exchange.stage_s": (
        {"exchange.stage_seconds": COUNTER}, 7.25, [{}]),
    "estimator.compile_s": (
        {"estimator.compile_seconds": COUNTER}, 7.25,
        [{}, {"estimator.compile_s": {"type": "gauge", "value": 3.0}}]),
    "estimator.dispatch_ms": (
        {"estimator.step.dispatch_ms": HIST}, 2.5,
        [{}, {"estimator.step.dispatch_ms": EMPTY_HIST},
         {"estimator.step.compute_ms": HIST}]),
    "estimator.restart_ms": (
        {"estimator.epoch.restart_ms": HIST}, 2.5,
        [{}, {"estimator.epoch.restart_ms": EMPTY_HIST}]),
    "estimator.mfu_program": (
        {"estimator.mfu": {"type": "gauge", "value": 0.00118},
         "estimator.steps_completed": {"type": "counter", "value": 640.0}},
        0.118,
        # the parent's gauge of that name was over dispatch time: not read
        [{}, {"estimator.mfu": {"type": "gauge", "value": 0.25}}]),
}


def _read(monkeypatch, name, snapshot):
    from raydp_tpu import obs

    monkeypatch.setattr(obs.metrics, "snapshot", lambda: snapshot)
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    return layers.read_metric(path, {})


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_gives_the_instruments_value(monkeypatch, name):
    snapshot, want, _ = CASES[name]
    assert _read(monkeypatch, name, snapshot) == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(CASES))
def test_reader_gives_none_where_the_instrument_is_missing(monkeypatch, name):
    for snapshot in CASES[name][2]:
        assert _read(monkeypatch, name, snapshot) is None


@pytest.mark.parametrize("cell", ["dlrm-criteo-kaggle.etl-stream",
                                  "dlrm-criteo-kaggle.fit-resident"])
def test_both_cells_resolve_with_the_new_entries(monkeypatch, cell):
    c = cells.resolve(ROOT, cell)
    by_name = {m["name"]: m for m in c.per_layer}
    assert set(CASES) <= set(by_name)
    end_to_end = {m["name"] for m in c.end_to_end}
    for name in CASES:
        assert c.layer_files[name].endswith(name + ".py")
        assert by_name[name]["source"] == "program_span"
        assert by_name[name]["moves"] in end_to_end
    # a result line takes the five beside the old ones, and leaves out what
    # had nothing to read
    from raydp_tpu import obs

    snapshot = {}
    for name in sorted(CASES)[:-1]:
        snapshot.update(CASES[name][0])
    monkeypatch.setattr(obs.metrics, "snapshot", lambda: snapshot)
    got = layers.read_all(c, {"values": {"etl_query_s": 0.5}})
    assert set(got) == {"etl.query_s", *sorted(CASES)[:-1]}
    assert got["estimator.dispatch_ms"] == {"value": 2.5, "unit": "ms"}


def test_the_new_entries_are_the_last_five_and_nothing_else_moved():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [m["name"] for m in bench["per_layer"][-5:]] == [
        "exchange.stage_s", "estimator.compile_s", "estimator.dispatch_ms",
        "estimator.restart_ms", "estimator.mfu_program"]
    assert bench["per_layer"][-1]["unit"] == "%"
    assert len(bench["per_layer"]) == 13 and len(bench["workloads"]) == 2
