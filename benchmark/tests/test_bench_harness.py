"""The harness's own arithmetic and file resolution (no jax needed)."""

import json
import os
import shutil
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import (  # noqa: E402
    cells, costs, criteo, layers, peaks, stats, xplane)
from benchmark.harness.xplane import TraceSummary  # noqa: E402


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- resolution by name ------------------------------------------------------


@pytest.mark.parametrize("cell", [w["name"] for w in bench()["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = cells.resolve(ROOT, cell)
    assert c.name == f"{c.config_name}.{c.traffic_name}"
    assert os.path.isfile(c.driver_path)
    assert c.config["name"] == c.config_name
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    for metric in c.per_layer:
        assert os.path.isfile(c.layer_files[metric["name"]])
        assert metric["moves"] in {m["name"] for m in c.end_to_end}


def _copy(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    return root


def test_unknown_workload_fails_loudly():
    with pytest.raises(cells.CellError, match="not in BENCHMARK.json"):
        cells.resolve(ROOT, "no-such.cell")


@pytest.mark.parametrize("victim,match", [
    ("benchmark/configs/dlrm-criteo-kaggle.json", "configuration"),
    ("benchmark/traffic/etl-stream.json", "traffic mix"),
    ("benchmark/drivers/fit.py", "traffic kind"),
    ("benchmark/layer_metrics/etl.query_s.json", "per-layer metric"),
])
def test_missing_file_fails_loudly_and_names_it(tmp_path, victim, match):
    root = _copy(tmp_path)
    os.remove(root / victim)
    with pytest.raises(cells.CellError, match=match) as err:
        cells.resolve(str(root), "dlrm-criteo-kaggle.etl-stream",
                      str(root / "benchmark"))
    assert os.path.basename(victim).split(".")[0] in str(err.value)


def test_new_files_and_entries_alone_add_a_cell(tmp_path):
    """A configuration, a traffic mix, a per-layer metric and a traffic kind
    are each added by new files and new BENCHMARK.json entries; no file that
    was there is edited."""
    root = _copy(tmp_path)
    b = root / "benchmark"
    before = {p: p.read_bytes() for p in b.rglob("*") if p.is_file()}
    (b / "configs" / "new-model.json").write_text(json.dumps(
        {"name": "new-model", "model": {"x": 1}, "rehearsal": {"model": {"x": 2}}}))
    (b / "traffic" / "new-mix.json").write_text(json.dumps(
        {"kind": "new_kind", "rate": 3}))
    (b / "drivers" / "new_kind.py").write_text(
        "def phases(trace):\n    return ['only']\n"
        "def run_phase(ctx):\n    ctx.write(ctx.phase, {'correct': {'ran': True}})\n")
    (b / "layer_metrics" / "new.counter.json").write_text(json.dumps(
        {"reader": "counter", "metric": "some.counter"}))
    (b / "layer_metrics" / "new.custom.py").write_text(
        "def read(sources):\n    return sources['values'].get('x')\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "new-model", "source": "paper",
                            "file": "benchmark/configs/new-model.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "new-model.new-mix", "config": "new-model",
                              "traffic": "new-mix", "chips": 1, "why": "test"})
    spec["end_to_end"].append({"name": "new_rate", "unit": "x/s",
                               "better": "higher", "bound": 0.05,
                               "source": "host_clock",
                               "workloads": ["new-model.new-mix"]})
    for name in ("new.counter", "new.custom"):
        spec["per_layer"].append({
            "name": name, "unit": "n", "better": "higher",
            "source": "program_counter", "layer": "serving", "moves": "new_rate",
            "workloads": ["new-model.new-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    cell = cells.resolve(str(root), "new-model.new-mix", str(b))
    assert cell.kind == "new_kind" and cell.traffic["rate"] == 3
    assert cells.sized(cell.config, True)["model"] == {"x": 2}
    assert cells.load_module(cell.driver_path, "kind").phases(False) == ["only"]
    got = layers.read_all(cell, {"counters": {"some.counter": 7.0},
                                 "values": {"x": 2.5}})
    assert got == {"new.counter": {"value": 7.0, "unit": "n"},
                   "new.custom": {"value": 2.5, "unit": "n"}}
    assert {m["name"] for m in cell.end_to_end} == {"new_rate", "setup_s"}
    # the old cells are untouched and no old file changed
    cells.resolve(str(root), "dlrm-criteo-kaggle.etl-stream", str(b))
    assert all(p.read_bytes() == data for p, data in before.items())


def test_result_line_has_exactly_the_contract_keys():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 5}
    line = json.loads(cells.result_line(True, 3, 0, {"m": {"value": 1.0, "unit": "s"}}, device))
    assert set(line) == {"correct", "attempted", "failed", "metrics", "device"}
    traced = json.loads(cells.result_line(
        False, 3, 1, {}, device, {"device_ops": [], "idle_gaps": []}))
    assert set(traced) == {"correct", "attempted", "failed", "metrics",
                           "device", "breakdown"}
    assert traced["correct"] is False and traced["failed"] == 1


# -- metric arithmetic -------------------------------------------------------


def test_fenced_rate_uses_only_fences_inside_the_window():
    fences = [(0.5, 10), (1.0, 20), (2.0, 40), (3.5, 70), (4.2, 84)]
    r = stats.fenced_rate(fences, t_open=1.0, seconds=3.0)
    assert r == {"work": 50, "elapsed_s": 2.5, "rate": 20.0, "fences": 3}
    assert stats.fenced_rate(fences, 1.2, 0.5) is None  # fewer than two


def test_iqr_share_is_the_contracts_spread():
    import statistics
    values = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q = statistics.quantiles(values, n=4)
    assert stats.iqr_share(values) == pytest.approx((q[2] - q[0]) / q[1])


# -- peaks and kernel costs, against hand counts -----------------------------


def test_peaks_table_knows_the_v5e_and_refuses_others():
    row = peaks.peaks_for("TPU v5 lite")
    assert row["flops_per_s"] == 197e12 and row["hbm_bytes_per_s"] == 819e9
    assert "v5e" in row["source"]
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks_for("TPU v9")


def test_dot_interaction_cost_by_hand():
    # 2 rows, 3 features of 4 floats: 3 pairs x 4 multiply-adds x 2 rows
    c = costs.dot_interaction(batch=2, features=3, dim=4, itemsize=4)
    assert c["flops"] == 2 * 2 * 3 * 4 == 48
    assert c["bytes"] == 2 * 3 * 4 * 4 + 2 * 3 * 4 == 120
    # the cell's shape: 2048 x 27 x 16 float32 -> 351 pairs
    c = costs.dot_interaction(2048, 27, 16, 4)
    assert c["flops"] == 2 * 2048 * 351 * 16
    assert c["bytes"] == 2048 * 27 * 16 * 4 + 2048 * 351 * 4
    r = costs.roofline(c, peaks.peaks_for("TPU v5 lite"))
    assert r["bound"] == "bytes"
    assert r["min_s"] == pytest.approx(c["bytes"] / 819e9)


def test_dlrm_step_flops_by_hand():
    # dense 2 -> [3] -> 2 (embed), one table: 2 features, 1 pair;
    # top: (2 + 1) -> [4] -> 1
    fwd = 2 * (2 * 3 + 3 * 2) + 2 * 1 * 2 + 2 * (3 * 4 + 4 * 1)
    assert costs.dlrm_step_flops(5, 2, 2, [3], [4], 1) == 3 * 5 * fwd


# -- per-layer readers on synthetic sources ----------------------------------


def _write(tmp_path, name, spec):
    path = tmp_path / name
    path.write_text(json.dumps(spec))
    return str(path)


def test_histogram_reader_is_the_windows_mean(tmp_path):
    path = _write(tmp_path, "h.json", {"reader": "histogram", "metric": "a.b",
                                       "stat": "mean", "scale": 1000.0})
    src = {"histograms": {"a.b": {"count": 4, "sum": 2.0}}}
    assert layers.read_metric(path, src) == 500.0
    assert layers.read_metric(path, {"histograms": {"a.b": {"count": 0, "sum": 0}}}) is None
    assert layers.read_metric(path, {}) is None


def test_trace_readers(tmp_path):
    trace = TraceSummary(
        window_s=2.0, busy_s=0.5, devices=1,
        ops={"fusion.1": (10, 0.3), "my_interaction_kernel": (4, 0.002)},
        device_ops=[], idle_gaps=[])
    idle = _write(tmp_path, "i.json", {"reader": "trace_idle_share"})
    assert layers.read_metric(idle, {"trace": trace}) == 75.0
    assert layers.read_metric(idle, {"trace": None}) is None
    roof = _write(tmp_path, "r.json", {"reader": "trace_kernel_roofline",
                                       "op_pattern": "interaction",
                                       "cost": "dot_interaction"})
    cost = {"flops": 197e12 * 1e-4, "bytes": 819e9 * 2e-4}  # bytes-bound: 200 us
    src = {"trace": trace, "peaks": peaks.peaks_for("TPU v5 lite"),
           "kernels": {"dot_interaction": {"cost": cost}}}
    # 4 calls x 200 us least time over 2 ms measured = 40 %
    assert layers.read_metric(roof, src) == pytest.approx(40.0)
    src["trace"] = TraceSummary(2.0, 0.5, 1, {"fusion.1": (10, 0.3)}, [], [])
    assert layers.read_metric(roof, src) is None  # nothing to read


def test_per_step_trace_readers(tmp_path):
    table = "%f.1 = (f32[700000,16]{0,1:T(8,128)}, f32[700000]{0}) fusion(f32[64,16]{1,0} %x)"
    fill = "%b.2 = f32[700000,16]{0,1:T(8,128)} broadcast(f32[] %c)"
    gather = "%g.3 = f32[64,16]{1,0:T(8,128)} fusion(f32[700000,16]{0,1:T(8,128)} %t)"
    small = "%s.4 = f32[583,16]{1,0} fusion(f32[583,16]{1,0} %t)"
    evals = "%e.5 = f32[64,1]{1,0} fusion(f32[64,351]{1,0} %z)"
    trace = TraceSummary(
        window_s=1.0, busy_s=0.8, devices=1,
        ops={table: (20, 0.4), fill: (20, 0.1), gather: (20, 0.05),
             small: (20, 0.01), evals: (3, 0.02)},
        device_ops=[], idle_gaps=[])
    # most operations ran 20 times: 20 steps were traced
    assert xplane.steps_traced(trace.ops) == 20
    per = _write(tmp_path, "t.json", {
        "reader": "trace_ops_ms_per_step",
        "result_pattern": r"f32\[\d{5,},16\]"})
    # the table-shaped RESULTS (update and zero-fill), not the gather that
    # only reads a table, not the small table: 0.5 s over 20 steps
    assert layers.read_metric(per, {"trace": trace}) == pytest.approx(25.0)
    busy = _write(tmp_path, "b.json", {"reader": "trace_busy_ms_per_step"})
    assert layers.read_metric(busy, {"trace": trace}) == pytest.approx(40.0)
    assert layers.read_metric(per, {"trace": None}) is None
    nothing = TraceSummary(1.0, 0.1, 1, {evals: (3, 0.02)}, [], [])
    assert layers.read_metric(per, {"trace": nothing}) is None


def test_unknown_reader_kind_is_an_error(tmp_path):
    with pytest.raises(ValueError, match="reader"):
        layers.read_metric(_write(tmp_path, "x.json", {"reader": "guess"}), {})


# -- the row generator --------------------------------------------------------


def test_raw_frame_is_a_function_of_the_seed():
    cards = [50, 3, 100000]
    t1, r1 = criteo.raw_frame(2**31 + 11, 512, 2, cards, 1.05)
    t2, r2 = criteo.raw_frame(2**31 + 11, 512, 2, cards, 1.05)
    t3, r3 = criteo.raw_frame(2**31 + 12, 512, 2, cards, 1.05)
    assert t1.equals(t2) and not t1.equals(t3)
    assert t1.column_names == ["i0", "i1", "c0", "c1", "c2", "label"]
    strings = t1.column("c2").to_pylist()
    assert all(len(s) == 8 and int(s, 16) == int(v)
               for s, v in zip(strings, r1["c2"]))
    assert len(set(r1["c1"].tolist())) <= 3
    assert set(np.unique(r1["label"])) <= {0.0, 1.0}


def test_bounded_zipf_stays_in_range_and_is_skewed():
    ranks = criteo.bounded_zipf(np.random.default_rng(0), 1.05, 1000, 20000)
    assert ranks.min() >= 0 and ranks.max() < 1000
    assert (ranks == 0).mean() > 5 * (ranks == 500).mean()
