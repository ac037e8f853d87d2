"""The ``lmpretrain_routed`` kind's files (PR 34): the cell resolves, the
configuration carries every published number, ``moe_costs`` against a hand
count, the expert layer's three readers on a recorded trace of the layer (and
None without one), the comparison's own pieces (where two selections differ,
the router's groups), the reference's independence of the program, the cell's
rehearsal through every phase, and a program without the model."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, layers, moe_costs, peaks, xplane  # noqa: E402

CELL = "lfm2-8b-a1b.pretrain-8k-routed"
METRICS = os.path.join(ROOT, "benchmark", "layer_metrics")
PEAKS = peaks.peaks_for("TPU v5 lite")
TRACE = os.path.join(ROOT, "benchmark", "tests", "data", "moe_trace.xplane.pb")
# benchmark/tests/record_moe_trace.py's sizes and what it printed
RECORDED = {"tokens": 2048, "per_token": 2, "total": 8, "held": 2,
            "hidden": 512, "width": 256, "rows": 4096}
RECORDED_PAIRS = 961  # the held experts' load of a call: 611 + 350


def test_the_cell_resolves_with_its_driver_readers_and_traffic():
    cell = cells.resolve(ROOT, CELL)
    assert cell.kind == "lmpretrain_routed" and cell.chips == 1
    assert cell.driver_path.endswith("drivers/lmpretrain_routed.py")
    assert {m["name"] for m in cell.per_layer} == {
        "etl.query_s", "exchange.stage_s", "estimator.compile_s",
        "estimator.dispatch_ms", "estimator.restart_ms", "estimator.mfu",
        "estimator.mfu_program", "estimator.tok_s_program",
        "device.idle_share.fit", "device.lm_step_ms", "model.exit_loss_ms",
        "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
        "model.moe_ms", "kernel.moe_gmm_roofline",
        "model.moe_load_max_over_mean"}
    assert {m["name"] for m in cell.end_to_end} == {"fit_samples_per_s", "setup_s"}
    t = cell.traffic
    assert (t["seq_len"], t["batch"], t["train_rows"], t["held_out_rows"],
            t["rows"], t["zipf_a"], t["bigram_tilt"], t["streaming"],
            t["warmup_epochs"], t["trace_epochs"]) == (
        8192, 4, 12, 4, 64, 1.1, 0.5, False, 6, 3)
    for mode in ("as_run", "matched"):
        assert set(t["selection_tolerance"][mode]) == {
            "differ_share_max", "margin_max"}
        assert set(t["arith_tolerance"][mode]) == {
            "loss_abs", "logits_rel", "grads_rel"}
    model = cell.config["model"]
    assert model["class"] == "raydp_tpu.models.RoutedHybridLM"
    assert model["reference"] == "benchmark.reference.lfm2_moe"
    assert model["costs"] == "benchmark.harness.moe_costs"
    small = cells.sized(cell.config, rehearsal=True)
    assert (small["hidden_size"], small["num_experts"],
            small["share"]["experts_total"], small["num_experts_per_tok"],
            small["moe_intermediate_size"], small["vocab_size"]) == (
        64, 2, 8, 2, 32, 256)
    assert small["share"]["first_layer"] == 1  # the same five layer kinds


def test_entries_the_benchmark_had_are_where_they_were():
    """``test_bench_hybrid.py``'s rule one PR on (its own pins the benchmark
    at four cells and twenty metrics, false since this PR and not this PR's
    to edit: PERF.md, Open questions): the 20 per-layer metrics, 3
    configurations and 4 cells the benchmark had are its first, in their
    order; this PR's are after them; a metric's list of cells only grew at
    its end."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    had = ["etl.query_s", "exchange.ingest_ms", "exchange.h2d_ms",
           "estimator.table_update_ms", "estimator.mfu",
           "kernel.interaction_roofline", "device.step_ms",
           "device.idle_share.fit", "exchange.stage_s", "estimator.compile_s",
           "estimator.dispatch_ms", "estimator.restart_ms",
           "estimator.mfu_program", "kernel.flash_fwd_roofline",
           "kernel.flash_bwd_roofline", "model.exit_loss_ms",
           "device.lm_step_ms", "estimator.tok_s_program", "model.ssd_ms",
           "kernel.ssd_roofline"]
    names = [m["name"] for m in bench["per_layer"]]
    assert names[:len(had)] == had
    assert names[len(had):len(had) + 3] == [
        "model.moe_ms", "kernel.moe_gmm_roofline",
        "model.moe_load_max_over_mean"]
    cells_had = ["dlrm-criteo-kaggle.etl-stream",
                 "dlrm-criteo-kaggle.fit-resident", "ouro-2.6b.pretrain-4k",
                 "granite-4.0-h-micro.pretrain-8k"]
    assert [w["name"] for w in bench["workloads"]][:5] == cells_had + [CELL]
    assert [c["name"] for c in bench["configs"]][:4] == [
        "dlrm-criteo-kaggle", "ouro-2.6b", "granite-4.0-h-micro",
        "lfm2-8b-a1b"]
    for m in bench["per_layer"][:len(had) + 3] + bench["end_to_end"]:
        listed = m.get("workloads")
        if listed is not None:
            old = [w for w in listed if w in cells_had]
            assert listed[:len(old)] == old, m["name"]
            assert listed[len(old):len(old) + 1] in ([], [CELL]), m["name"]
    assert bench["run_seconds"] == 20 and [
        (m["name"], m["bound"]) for m in bench["end_to_end"]] == [
        ("fit_samples_per_s", 0.01), ("setup_s", 0.1)]
    # the scan's metrics have nothing to read in a model without a scan
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert CELL not in by_name["model.ssd_ms"]["workloads"]


def test_every_published_number_is_in_the_configuration_file():
    """Against the catalog's row where it can be read (the builder's
    sandbox), and against the widths ISSUE 34 lists wherever the test runs."""
    c = cells.resolve(ROOT, CELL).config
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["intermediate_size"], c["moe_intermediate_size"],
            c["conv_L_cache"], c["num_experts_per_tok"], c["rope_theta"],
            c["norm_eps"], c["routed_scaling_factor"]) == (
        2048, 32, 8, 7168, 1792, 3, 4, 1000000, 1e-5, 1)
    assert (c["conv_bias"], c["norm_topk_prob"], c["use_expert_bias"],
            c["model_type"]) == (False, True, True, "lfm2_moe")
    assert len(c["layer_types"]) == 24
    assert c["layer_types"].count("full_attention") == 6
    assert c["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert (c["num_hidden_layers"], c["num_dense_layers"], c["num_experts"],
            c["vocab_size"]) == (5, 1, 8, 16384)
    assert c["reduced"] == ["num_hidden_layers", "num_dense_layers",
                            "num_experts", "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 24, "num_dense_layers": 2,
                              "num_experts": 32, "vocab_size": 65536}
    assert c["share"]["experts_total"] == 32 and c["share"]["chips_per_layer"] == 4
    assert (c["share"]["first_layer"], c["share"]["first_expert"]) == (1, 0)
    assert "4 chips" in c["stands_for"] and c["assumed"]["expert_bias"]
    assert any("expert_bias" in d for d in c["departures_from_source"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == "lfm2-8b-a1b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-8B-A1B")
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert c["published"].get(key, c[key]) == value, key
        assert (key in c["reduced"]) == (c[key] != value), key


def test_costs_match_a_hand_count():
    c = cells.resolve(ROOT, CELL).config
    batch, t = 4, 8192
    parts = moe_costs.step_flops(c, batch, t)
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    tokens = batch * t
    assert parts["layers"] == 6 * (
        4 * conv + attention + 3 * 2048 * 7168 + 4 * 2048 * 32) * tokens
    # the uniform share: tokens x 4 x 8 / 32 pairs an expert layer
    assert moe_costs.uniform_pairs(c, tokens) == tokens
    assert parts["experts"] == 4 * 18 * 2048 * 1792 * tokens
    assert parts["attention"] == 12 * 2048 * (t * (t + 1) // 2) * batch
    assert parts["head"] == 6 * 2048 * 16384 * tokens
    assert parts["total"] == sum(v for k, v in parts.items() if k != "total")
    # ISSUE 34's reckoning: 1.30e9 a token, 4.25e13 a step
    assert parts["total"] == pytest.approx(4.25e13, rel=0.01)
    assert parts["experts"] / parts["total"] == pytest.approx(0.20, abs=0.02)
    kernels = moe_costs.kernels(c, batch, t)
    gmm = kernels["moe_gmm"]
    assert gmm["layers"] == 4
    # a pair: 6 D F forward, 12 D F backward; bf16 rows in and out
    assert gmm["per_pair"]["flops"] == 18 * 2048 * 1792
    assert gmm["per_pair"]["bytes"] == (5 * 2048 + 7 * 1792) * 2
    assert gmm["weights_per_layer_step"]["bytes"] == 3 * 8 * 3 * 2048 * 1792 * 2
    # the flash kernels see the 32 query heads of 64, four sequences a call
    assert kernels["flash_fwd"]["cost"]["flops"] == 4 * 32 * 4 * 64 * (
        t * (t + 1) // 2)
    values = moe_costs.reader_values(c, batch, t)
    assert values["moe_axes"]["tokens"] == tokens
    assert (values["moe_axes"]["total"], values["moe_axes"]["held"]) == (32, 8)


def read(name, src):
    for ext in (".py", ".json"):
        path = os.path.join(METRICS, name + ext)
        if os.path.exists(path):
            return layers.read_metric(path, src)
    raise FileNotFoundError(name)


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce_trace(TRACE)


def sources(trace, **values):
    pair = moe_costs.gmm_pair(512, 256, 2)
    return {"trace": trace, "peaks": PEAKS, "values": values, "kernels": {
        "moe_gmm": {"per_pair": {k: pair["fwd"][k] + pair["bwd"][k]
                                 for k in ("flops", "bytes")},
                    "weights_per_layer_step": moe_costs.gmm_weights(
                        512, 256, 2, 2), "layers": 1}}}


def test_the_expert_layers_readers_on_a_recorded_trace_of_the_layer(recorded):
    """Two calls of the forward-and-backward expert layer and of a matmul
    that is no part of it: the readers find the layer's operations by the
    kernels' names and the arrays' shapes, most of the device's busy time
    and not the matmul; the roofline share is a share."""
    # six kernel calls a call of the layer: 2 forward, 4 backward
    calls = sum(n for name, (n, _) in recorded.ops.items()
                if moe_costs.GMM.search(name))
    steps = calls // 6
    assert steps in (1, 2) and calls == 6 * steps
    src = sources(recorded, steps_in_trace=steps, moe_axes=RECORDED,
                  moe_pairs_in_trace=steps * RECORDED_PAIRS,
                  moe_steps_reported_in_trace=steps,
                  moe_load_max_over_mean=1.07)
    seconds = moe_costs.moe_seconds(recorded.ops, RECORDED)
    kernel_s = moe_costs.gmm_seconds(recorded.ops)
    matmul = sum(s for name, (_, s) in recorded.ops.items()
                 if xplane.result_type(name).startswith("bf16[1024,1024]"))
    assert matmul > 0 and 0 < kernel_s < seconds <= recorded.busy_s - matmul + 1e-9
    assert seconds >= 0.7 * (recorded.busy_s - matmul)
    assert read("model.moe_ms", src) == pytest.approx(1e3 * seconds / steps)
    share = read("kernel.moe_gmm_roofline", src)
    assert 0 < share < 100
    needed = {"flops": steps * RECORDED_PAIRS * 18 * 512 * 256,
              "bytes": steps * RECORDED_PAIRS * (5 * 512 + 7 * 256) * 2
              + steps * 3 * 2 * 3 * 512 * 256 * 2}
    least = max(needed["flops"] / PEAKS["flops_per_s"],
                needed["bytes"] / PEAKS["hbm_bytes_per_s"])
    assert share == pytest.approx(100 * least / kernel_s)
    assert read("model.moe_load_max_over_mean", src) == 1.07


def test_the_readers_give_none_where_there_is_nothing_to_read(recorded):
    full = dict(steps_in_trace=2, moe_axes=RECORDED,
                moe_pairs_in_trace=2 * RECORDED_PAIRS,
                moe_steps_reported_in_trace=2)
    for name in ("model.moe_ms", "kernel.moe_gmm_roofline"):
        assert read(name, {"trace": None}) is None
        assert read(name, sources(None, **full)) is None
        assert read(name, sources(recorded, **{
            **full, "steps_in_trace": None})) is None  # no count of steps
    # a program without the layer: no axes, no count of pairs, no gauge
    assert read("model.moe_ms", sources(
        recorded, steps_in_trace=2, moe_axes=None)) is None
    assert read("model.moe_ms", sources(recorded, steps_in_trace=2, moe_axes={
        **RECORDED, "rows": None})) is None
    # no count of the stretch's pairs, or one of another stretch's steps
    assert read("kernel.moe_gmm_roofline", sources(
        recorded, **{**full, "moe_pairs_in_trace": None})) is None
    assert read("kernel.moe_gmm_roofline", sources(
        recorded, **{**full, "moe_steps_reported_in_trace": 3})) is None
    assert read("kernel.moe_gmm_roofline", {
        **sources(recorded, **full), "kernels": {}}) is None
    assert read("model.moe_load_max_over_mean", sources(recorded)) is None
    # a trace that holds none of the layer's kernels or shapes
    other = xplane.reduce_trace(os.path.join(
        ROOT, "benchmark", "tests", "data", "ssd_trace.xplane.pb"))
    assert read("kernel.moe_gmm_roofline", sources(other, **full)) is None
    assert read("model.moe_ms", sources(other, **full)) is None
    # the program's gauges, read where the program has none
    values = moe_costs.reader_values(cells.resolve(ROOT, CELL).config, 4, 8192)
    assert values["moe_pairs_in_trace"] is None
    assert values["moe_steps_reported_in_trace"] is None
    assert values["moe_load_max_over_mean"] is None
    assert values["moe_axes"]["rows"] == 4 * 8192 * 4  # the worst case


def test_where_two_selections_differ():
    from benchmark.drivers import lmpretrain_routed as routed

    ref_out = {"selection": np.array([[[[0, 1], [2, 3], [4, 5]]],
                                      [[[1, 0], [6, 7], [2, 5]]]]),
               "margin": np.array([[[0.5, 0.001, 0.2]], [[0.3, 0.004, 0.1]]])}
    program = np.array([[[[1, 0], [2, 4], [4, 5]]],   # the order is no matter
                        [[[0, 1], [6, 5], [5, 2]]]])
    got = routed.selection_gaps(program, ref_out)
    assert got["decisions"] == 6 and got["per_layer"] == [1, 1]
    assert got["differ_share"] == pytest.approx(2 / 6)
    assert got["margin_worst"] == 0.004
    same = routed.selection_gaps(ref_out["selection"], ref_out)
    assert (same["differ_share"], same["margin_worst"]) == (0.0, 0.0)


def test_the_stretchs_pairs_are_the_counters_growth_between_its_fences(
        monkeypatch):
    """The driver notes the program's cumulative counters where the profiler
    starts and stops; the readers get their growth, and nothing from a
    program without the counters."""
    config = cells.resolve(ROOT, CELL).config
    counters = {"model.experts.pairs_held": 1000.0,
                "model.experts.steps_reported": 21.0}
    monkeypatch.setattr(moe_costs, "_fences", [])
    monkeypatch.setattr(moe_costs, "_program_value", counters.get)
    moe_costs.note_fence()
    assert moe_costs.reader_values(config, 4, 8192)[
        "moe_pairs_in_trace"] is None  # one fence is no stretch
    counters.update({"model.experts.pairs_held": 1000.0 + 9 * 130000,
                     "model.experts.steps_reported": 30.0})
    moe_costs.note_fence()
    values = moe_costs.reader_values(config, 4, 8192)
    assert values["moe_pairs_in_trace"] == 9 * 130000
    assert values["moe_steps_reported_in_trace"] == 9
    monkeypatch.setattr(moe_costs, "_fences", [
        {name: None for name in moe_costs.STRETCH_COUNTERS}] * 2)
    assert moe_costs.reader_values(config, 4, 8192)[
        "moe_pairs_in_trace"] is None


def test_the_references_optimizer_is_adamw_under_a_warm_up_and_the_rule():
    """``lfm2_moe.adamw_step``: the biases take ``b -= rate x excess`` and
    nothing of AdamW; the others AdamW at the warm-up's rate of the step."""
    from benchmark.reference import granite_hybrid, lfm2_moe

    rng = np.random.default_rng(0)
    first = [rng.standard_normal((4, 3)).astype(np.float32),
             rng.standard_normal(5).astype(np.float32)]
    grads = [[rng.standard_normal(a.shape).astype(np.float32) for a in first]
             for _ in range(3)]
    got, want = ([a.copy() for a in first] for _ in range(2))
    state, plain = lfm2_moe.adamw_init(got), granite_hybrid.adamw_init(want)
    for i, g in enumerate(grads):
        got, state = lfm2_moe.adamw_step(
            got, g, state, 3e-4, 0.9, 0.95, 0.1, warmup_steps=4,
            expert_bias_rate=0.02, biases=[1])
        want, plain = granite_hybrid.adamw_step(
            want, g, plain, 3e-4 * (i + 1) / 4, 0.9, 0.95, 0.1)
    assert np.array_equal(got[0], want[0])
    assert np.allclose(got[1], first[1] - 0.02 * sum(g[1] for g in grads),
                       atol=1e-7)
    assert lfm2_moe.bias_leaves({"params": {
        "embed": 0, "layer_1": {"expert_bias": 0, "router": 0}}}) == [1]


def test_the_references_copy_imports_nothing_from_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "lfm2_moe.py")) as f:
        text = f.read()
    assert "import raydp_tpu" not in text and "from raydp_tpu" not in text
    # no kernel, no sort, no grouped product: every expert on every token
    for name in ("ragged_dot", "gmm", "lax.sort", "argsort", "pallas"):
        assert name not in text, name
    assert 'default_matmul_precision("highest")' in text


def test_rehearsal_runs_every_phase_of_the_cell():
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 5), "--seconds", "2",
         "--trace", "1", "--rehearse-on-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["metrics"] == {} and last["failed"] == 0 and last["attempted"] > 0
    for part in ("a_arithmetic", "b_data", "c_fit_trains", "d_window"):
        assert f"correct[{part}] = True" in proc.stdout
    assert "pairs dropped 0 (must be 0)" in proc.stdout
    assert "(i) selection: 0 of 256 (token, layer) choices differ" in proc.stdout


def test_a_program_without_the_model_leaves_at_once(tmp_path):
    """The parent commit's ``raydp_tpu`` has ``HybridLM`` and no
    ``RoutedHybridLM``: the phase leaves before it starts a cluster, with a
    message, and the run prints no result."""
    import shutil

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    package = tmp_path / "raydp_tpu"
    (package / "models").mkdir(parents=True)
    (package / "__init__.py").write_text("")
    (package / "models" / "__init__.py").write_text("class HybridLM: pass\n")
    env = dict(os.environ, PYTHONPATH=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload",
         CELL, "--rehearse-on-cpu"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "cannot run configuration 'lfm2-8b-a1b'" in proc.stdout
    assert "RoutedHybridLM" in proc.stdout
    assert '"correct"' not in proc.stdout and "init_etl" not in proc.stdout
