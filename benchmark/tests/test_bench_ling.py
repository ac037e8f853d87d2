"""The KDA / latent-attention cell's files (PR 49): the cell resolves with
every reader it lists, the configuration carries every number of the
catalog's row, ``ling_costs`` against a hand count, the two new scope readers
on a hand-made scope table (and None without a map), the reference's
independence of the program, the cell's rehearsal through every phase, and a
program without the model."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import (  # noqa: E402
    cells, costs, layers, ling_costs, peaks, scopes)

CELL = "ling-3.0-flash.pretrain-8k-kda"
OLMO = "olmo-hybrid-7b.pretrain-8k-delta"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "first_k_dense_replace", "num_experts",
           "vocab_size"]


def test_the_cell_resolves_with_its_driver_readers_and_traffic():
    cell = cells.resolve(ROOT, CELL)
    assert cell.kind == "lmpretrain_routed" and cell.chips == 1
    assert cell.driver_path.endswith("drivers/lmpretrain_routed.py")
    assert {m["name"] for m in cell.per_layer} == {
        "etl.query_s", "exchange.stage_s", "estimator.compile_s",
        "estimator.dispatch_ms", "estimator.restart_ms", "estimator.mfu",
        "estimator.mfu_program", "estimator.optimizer_scope_ms",
        "device.idle_share.fit", "device.scope_unattributed_share",
        "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
        "device.lm_step_ms", "estimator.tok_s_program", "model.loss_scope_ms",
        "model.attention_scope_ms", "model.delta_scope_ms",
        "kernel.delta_rule_roofline", "model.experts_scope_ms",
        "kernel.moe_gmm_roofline", "model.moe_load_max_over_mean",
        "model.moe_likely_bound_share", "model.delta_mixer_scope_ms",
        "model.shared_expert_scope_ms"}
    assert set(cell.layer_files) == {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"fit_samples_per_s",
                                                    "setup_s"}
    t = cell.traffic
    assert (t["seq_len"], t["batch"], t["train_rows"], t["held_out_rows"],
            t["rows"], t["zipf_a"], t["bigram_tilt"], t["streaming"],
            t["warmup_epochs"], t["trace_epochs"],
            t["reference_token_block"]) == (
        8192, 1, 3, 1, 64, 1.1, 0.5, False, 6, 3, 2048)
    for mode in ("as_run", "matched"):
        assert set(t["selection_tolerance"][mode]) == {
            "differ_share_max", "margin_max"}
        assert set(t["arith_tolerance"][mode]) == {
            "loss_abs", "logits_rel", "grads_rel"}
        # every matched limit far under its as_run limit
        for key, value in t["arith_tolerance"]["matched"].items():
            assert value * 20 <= t["arith_tolerance"]["as_run"][key]
        for key, value in t["selection_tolerance"]["matched"].items():
            assert value * 20 <= t["selection_tolerance"]["as_run"][key]
    model = cell.config["model"]
    assert model["class"] == "raydp_tpu.models.LatentDeltaHybridLM"
    assert model["reference"] == "benchmark.reference.ling_hybrid"
    assert model["costs"] == "benchmark.harness.ling_costs"
    # the Olmo cell reports the mixer's scope too, and nothing else is new there
    olmo = {m["name"] for m in cells.resolve(ROOT, OLMO).per_layer}
    assert "model.delta_mixer_scope_ms" in olmo
    assert "model.shared_expert_scope_ms" not in olmo


def test_the_new_entries_are_the_last_and_nothing_else_moved():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == "ling-3.0-flash"
    assert bench["configs"][-1]["reduced"] == REDUCED
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "ling-3.0-flash", "traffic": "pretrain-8k-kda",
        "chips": 1, "why": bench["workloads"][-1]["why"]}
    assert len(bench["workloads"][-1]["why"]) <= 200
    assert [m["name"] for m in bench["per_layer"][-2:]] == [
        "model.delta_mixer_scope_ms", "model.shared_expert_scope_ms"]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        where = metric.get("workloads", [])
        # a list that gained the cell gained it at its end
        assert CELL not in where[:-1] or metric["name"] == (
            "model.delta_mixer_scope_ms")
    assert len(bench["workloads"]) == 8 and bench["run_seconds"] == 20


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalogs_row_is_in_the_configuration_file():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Ling-3.0-flash")
    cell = cells.resolve(ROOT, CELL)
    config = cell.config
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(REDUCED) == sorted(config["reduced"])
    assert config["published"] == {k: row["config"][k] for k in REDUCED}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["num_experts"], config["vocab_size"]) == (6, 1, 8, 19648)
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert config["share"]["experts_total"] == row["config"]["num_experts"]
    assert config["num_experts"] * config["share"]["chips_per_layer"] == 512
    for said in ("mtp", "kda", "mla", "experts", "layer_pattern"):
        assert config["assumed"][said]
    assert "mtp_loss_scaling_factor" in config["assumed"]["mtp"]


def test_costs_match_a_hand_count():
    config = cells.resolve(ROOT, CELL).config
    t = 8192
    flops = ling_costs.step_flops(config, 1, t)
    h = 2560
    kda = 3 * h * 4096 + h * 4096 + 2 * h * 32 + 4096 * h + 4 * 3 * 4096
    mla = h * 6144 + h * 576 + 512 * 8192 + h * 32 + 4096 * h
    ffn = 3 * h * 6144 + 5 * (h * 512 + 3 * h * 768)
    assert flops["layers"] == 6 * (5 * kda + mla + ffn) * t
    assert ling_costs.uniform_pairs(config, t) == 1024
    assert flops["experts"] == 5 * 6 * 3 * h * 768 * 1024
    assert flops["delta"] == 3 * 5 * 6 * 128 * 128 * 32 * t
    pairs = t * (t + 1) // 2
    assert flops["attention"] == 3 * 32 * 2 * (192 + 128) * pairs
    assert flops["head"] == 6 * h * 19648 * t
    assert flops["total"] == sum(v for k, v in flops.items() if k != "total")
    assert 2.2e13 < flops["total"] < 2.6e13  # the issue's ~2.4e13
    k = ling_costs.kernels(config, 1, t)
    assert k["flash_fwd"]["cost"] == {
        "flops": 32 * 2 * 320 * pairs,
        "bytes": 32 * (2 * t * 320 * 2 + 2 * t * 4)}
    assert k["flash_bwd"]["cost"] == {
        "flops": 32 * 4 * 320 * pairs,
        "bytes": 32 * (t * (4 * 192 + 3 * 128) * 2 + 2 * t * 4)}
    # equal widths: lm_costs' own count
    from benchmark.harness import lm_costs
    assert ling_costs.flash_fwd(2, 4, 256, 64, 64, 2) == lm_costs.flash_fwd(
        2, 4, 256, 64, 2)
    assert ling_costs.flash_bwd(2, 4, 256, 64, 64, 2) == lm_costs.flash_bwd(
        2, 4, 256, 64, 2)
    rows = t * 32
    assert k["delta_fwd"] == {"layers": 5, "cost": {
        "flops": rows * 6 * 128 * 128,
        "bytes": rows * (3 * 128 + 2 * 128 + 1) * 2}}
    assert k["delta_bwd"]["cost"]["flops"] == 2 * k["delta_fwd"]["cost"]["flops"]
    assert k["delta_bwd"]["cost"]["bytes"] == rows * (641 + 513) * 2
    assert k["moe_gmm"]["layers"] == 5
    assert k["moe_gmm"]["per_pair"]["flops"] == 18 * h * 768
    assert k["moe_gmm"]["weights_per_layer_step"]["bytes"] == 3 * 8 * 3 * h * 768 * 2
    # the scan at its roofline on a v5e: bytes bind (0.3 FLOPs a byte needed)
    line = costs.roofline(k["delta_fwd"]["cost"], peaks.peaks_for("TPU v5 lite"))
    assert line["bound"] == "bytes"
    values = ling_costs.reader_values(config, 1, t)
    assert values["moe_axes"] == {
        "tokens": t, "per_token": 8, "total": 512, "held": 8, "hidden": h,
        "width": 768, "rows": 65536}
    assert values["moe_pairs_in_trace"] is None  # no fence noted here


LAYOUT = "{1,0:T(8,128)(2,1)S(1)}"


def _line(name, result):
    return (f"%{name} = {result}{LAYOUT} fusion(bf16[8,16]{LAYOUT} %p.1), "
            "kind=kLoop")


STEP = {
    "fusion.1": {"result": "bf16[8,16]", "scopes": [
        "loss_and_grad", "hybridlm.delta", "delta_rule"]},
    "fusion.2": {"result": "f32[8,16]", "scopes": [
        "loss_and_grad", "hybridlm.delta"]},
    "fusion.3": {"result": "f32[16,8]", "scopes": [
        "loss_and_grad", "hybridlm.delta"], "mixed": True},
    "fusion.4": {"result": "bf16[16,8]", "scopes": [
        "loss_and_grad", "hybridlm.experts", "hybridlm.experts.shared"]},
    "fusion.5": {"result": "bf16[4,8]", "scopes": [
        "loss_and_grad", "hybridlm.experts", "hybridlm.experts.gmm"]},
    "fusion.6": {"result": "f32[4,8]", "scopes": ["optimizer_update"]},
}
OPS = {
    _line("fusion.1", "bf16[8,16]"): (15, 0.030),
    _line("fusion.2", "f32[8,16]"): (15, 0.012),
    _line("fusion.3", "f32[16,8]"): (9, 0.006),
    _line("fusion.4", "bf16[16,8]"): (9, 0.0045),
    _line("fusion.5", "bf16[4,8]"): (9, 0.009),
    _line("fusion.6", "f32[4,8]"): (9, 0.018),
}


def _read(name, sources):
    return layers.read_metric(
        os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"), sources)


@pytest.fixture()
def sources(monkeypatch):
    from raydp_tpu.obs import profiler

    monkeypatch.setattr(profiler, "device_scopes", lambda: {"3#1": STEP},
                        raising=False)
    monkeypatch.setattr(scopes, "_made", [])
    monkeypatch.setattr(scopes, "_printed", True)
    return {"trace": types.SimpleNamespace(ops=OPS),
            "values": {"steps_in_trace": 9}}


def test_the_two_new_readers_read_the_scope_table(sources):
    # under hybridlm.delta and OUTSIDE delta_rule: fusion.2 and fusion.3
    assert _read("model.delta_mixer_scope_ms", sources) == pytest.approx(
        1e3 * (0.012 + 0.006) / 9)
    assert _read("model.delta_scope_ms", sources) == pytest.approx(
        1e3 * 0.030 / 9)
    assert _read("model.shared_expert_scope_ms", sources) == pytest.approx(
        1e3 * 0.0045 / 9)
    # the shared expert lies inside the expert layer's scope
    assert _read("model.experts_scope_ms", sources) == pytest.approx(
        1e3 * (0.0045 + 0.009) / 9)


@pytest.mark.parametrize("name", ["model.delta_mixer_scope_ms",
                                  "model.shared_expert_scope_ms"])
def test_a_new_reader_gives_nothing_where_there_is_nothing_to_read(
        name, sources, monkeypatch):
    from raydp_tpu.obs import profiler

    assert _read(name, {"values": {"steps_in_trace": 9}}) is None  # no trace
    assert _read(name, {**sources, "values": {}}) is None  # no count
    monkeypatch.setattr(scopes, "_made", [])
    monkeypatch.setattr(profiler, "device_scopes", lambda: {
        "3#1": {"fusion.6": STEP["fusion.6"]}}, raising=False)
    assert _read(name, sources) is None  # a program without such a scope
    monkeypatch.setattr(scopes, "_made", [])
    monkeypatch.delattr(profiler, "device_scopes")  # a program without a map
    assert _read(name, sources) is None


def test_the_references_copy_imports_nothing_from_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "ling_hybrid.py")) as f:
        text = f.read()
    assert "import raydp_tpu" not in text and "from raydp_tpu" not in text
    # the recurrence token by token: no chunk, no solve, no kernel, no sort
    for name in ("triangular_solve", "cumsum", "ragged_dot", "gmm", "lax.sort",
                 "argsort", "pallas"):
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


def test_a_program_without_the_model_leaves_at_once(tmp_path):
    """The parent commit's ``raydp_tpu`` has ``HybridLM`` and no
    ``LatentDeltaHybridLM``: the phase leaves before it starts a cluster,
    with a message, and the run prints no result."""
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
    assert "cannot run configuration 'ling-3.0-flash'" in proc.stdout
    assert "LatentDeltaHybridLM" in proc.stdout
    assert '"correct"' not in proc.stdout and "init_etl" not in proc.stdout
