"""The latent-attention / multi-token-prediction cell's files (PR 53): the
cell resolves with every reader it lists, what the benchmark had is a prefix
of what it has, the configuration carries every number of the catalog's row,
``glm_costs`` against a hand count, the two new scope readers on a hand-made
scope table (and None without a map), the reference's independence of the
program, the cell's rehearsal through every phase, and a program without the
model."""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import (  # noqa: E402
    cells, costs, glm_costs, layers, lm_costs, peaks, scopes)

CELL = "glm-4.7-flash.pretrain-8k-mtp"
LING = "ling-3.0-flash.pretrain-8k-kda"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REDUCED = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
READERS = {
    "etl.query_s", "exchange.stage_s", "estimator.compile_s",
    "estimator.dispatch_ms", "estimator.restart_ms", "estimator.mfu",
    "estimator.mfu_program", "estimator.tok_s_program",
    "estimator.optimizer_scope_ms", "device.idle_share.fit",
    "device.lm_step_ms", "device.scope_unattributed_share",
    "kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
    "kernel.moe_gmm_roofline", "model.moe_load_max_over_mean",
    "model.moe_likely_bound_share", "model.experts_scope_ms",
    "model.shared_expert_scope_ms", "model.attention_scope_ms",
    "model.loss_scope_ms", "model.mtp_scope_ms", "model.latent_proj_scope_ms"}


def test_the_cell_resolves_with_its_driver_readers_and_traffic():
    cell = cells.resolve(ROOT, CELL)
    # lmpretrain_routed's run under lmpretrain_routed_placed's comparison
    # (the gap that does not average over the tokens), without the placement
    assert cell.kind == "lmpretrain_routed_tokens" and cell.chips == 1
    assert cell.driver_path.endswith("drivers/lmpretrain_routed_tokens.py")
    driver = cells.load_module(cell.driver_path, "the cell's driver")
    assert driver.GAPS[-1] == "token_loss_rms" and len(driver.GAPS) == 4
    assert driver.check_objective.__module__.endswith("lmpretrain_routed_placed")
    assert not hasattr(driver, "_start_placed")
    # what ISSUE 53 lists; a later PR's reader that applies here may join them
    assert READERS <= {m["name"] for m in cell.per_layer}
    assert set(cell.layer_files) == {m["name"] for m in cell.per_layer}
    assert {m["name"] for m in cell.end_to_end} == {"fit_samples_per_s",
                                                    "setup_s"}
    t = cell.traffic
    assert (t["seq_len"], t["batch"], t["held_out_rows"], t["rows"],
            t["zipf_a"], t["bigram_tilt"], t["streaming"],
            t["warmup_epochs"], t["trace_epochs"],
            t["reference_token_block"]) == (
        8192, 1, 1, 64, 1.1, 0.5, False, 6, 3, 2048)
    assert t["train_rows"] in (2, 3) and t["epoch_why"]
    for mode in ("as_run", "matched"):
        assert set(t["selection_tolerance"][mode]) == {
            "differ_share_max", "margin_max"}
        assert set(t["arith_tolerance"][mode]) == {
            "loss_abs", "logits_rel", "grads_rel", "token_loss_rms"}
        # every matched limit far under its as_run limit
        for key, value in t["arith_tolerance"]["matched"].items():
            assert value * 10 <= t["arith_tolerance"]["as_run"][key]
        for key, value in t["selection_tolerance"]["matched"].items():
            assert value * 10 <= t["selection_tolerance"]["as_run"][key]
    for why in ("why", "step_why"):
        assert len(t["arith_tolerance"][why]) > 500
    model = cell.config["model"]
    assert model["class"] == "raydp_tpu.models.LatentMTPHybridLM"
    assert model["reference"] == "benchmark.reference.glm_moe_lite"
    assert model["costs"] == "benchmark.harness.glm_costs"
    assert model["kwargs"]["mtp_weight"] == 0.3
    # the Ling cell reports the projections' scope too: nothing else is new there
    ling = {m["name"] for m in cells.resolve(ROOT, LING).per_layer}
    assert "model.latent_proj_scope_ms" in ling
    assert "model.mtp_scope_ms" not in ling


def test_the_new_entries_follow_what_the_benchmark_had():
    """Written so that the NEXT cell does not fail it: what PR 52's benchmark
    had stays a prefix, this PR's entries follow, whatever follows them."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    configs = [c["name"] for c in bench["configs"]]
    assert configs[:8] == [
        "dlrm-criteo-kaggle", "ouro-2.6b", "granite-4.0-h-micro",
        "lfm2-8b-a1b", "smallthinker-21b-a3b", "olmo-hybrid-7b",
        "ling-3.0-flash", "glm-4.7-flash"]
    assert bench["configs"][7]["reduced"] == REDUCED
    assert bench["workloads"][8] == {
        "name": CELL, "config": "glm-4.7-flash", "traffic": "pretrain-8k-mtp",
        "chips": 1, "why": bench["workloads"][8]["why"]}
    assert bench["workloads"][7]["name"] == LING
    assert len(bench["workloads"][8]["why"]) <= 200
    names = [m["name"] for m in bench["per_layer"]]
    assert names[36:38] == ["model.mtp_scope_ms", "model.latent_proj_scope_ms"]
    assert names[35] == "model.shared_expert_scope_ms"
    had = {w["name"] for w in bench["workloads"][:8]}
    for metric in bench["end_to_end"] + bench["per_layer"][:36]:
        where = metric.get("workloads", [])
        if CELL in where:
            # appended: every cell before it is one the benchmark had
            assert set(where[:where.index(CELL)]) <= had, metric["name"]
    assert bench["run_seconds"] == 20
    assert [m["bound"] for m in bench["end_to_end"][:2]] == [0.01, 0.1]


@pytest.mark.skipif(not os.path.isfile(CATALOG), reason="no catalog here")
def test_every_number_of_the_catalogs_row_is_in_the_configuration_file():
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "GLM-4.7-Flash")
    config = cells.resolve(ROOT, CELL).config
    assert config["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differ == sorted(REDUCED) == sorted(config["reduced"])
    assert config["published"] == {k: row["config"][k] for k in REDUCED}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 19360)
    assert config["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert config["share"]["experts_total"] == row["config"]["n_routed_experts"]
    assert config["n_routed_experts"] * config["share"]["chips_per_layer"] == 64
    for said in ("mtp", "mtp_weight", "mla", "experts", "expert_bias"):
        assert config["assumed"][said]
    assert "8 chips" in config["stands_for"] or "8-way" in config["stands_for"]
    assert any("published layer 47" in d
               for d in config["departures_from_source"])


def test_costs_match_a_hand_count():
    config = cells.resolve(ROOT, CELL).config
    t, h = 8192, 2048
    flops = glm_costs.step_flops(config, 1, t)
    mixer = (h * 768 + 768 * 5120 + h * 576 + 512 * 8960 + 5120 * h)
    assert mixer == 21_759_232 - 768 - 512  # ISSUE 53's count less the norms
    shared_and_router = h * 64 + 3 * h * 1536
    assert flops["layers"] == 6 * t * (
        6 * mixer + 3 * h * 10240 + 5 * shared_and_router + 2 * h * h)
    assert glm_costs.uniform_pairs(config, t) == 4096
    assert flops["experts"] == 5 * 6 * 3 * h * 1536 * 4096
    pairs = t * (t + 1) // 2
    assert flops["attention"] == 6 * 3 * 20 * 2 * 512 * pairs
    assert flops["head"] == 2 * 6 * h * 19360 * t
    assert flops["total"] == sum(v for k, v in flops.items()
                                 if k not in ("total", "mtp"))
    assert 2.9e13 < flops["total"] < 3.05e13
    # the module: a sixth block, eh_proj and the second head pass
    assert flops["mtp"] == (
        6 * t * (mixer + shared_and_router + 2 * h * h)
        + 6 * 3 * h * 1536 * 4096 + 3 * 20 * 2 * 512 * pairs
        + 6 * h * 19360 * t)
    assert 0.20 < flops["mtp"] / flops["total"] < 0.22
    k = glm_costs.kernels(config, 1, t)
    # equal widths of 256: lm_costs' own count
    assert k["flash_fwd"] == {"layers": 6, "cost": lm_costs.flash_fwd(
        1, 20, t, 256, 2)}
    assert k["flash_bwd"] == {"layers": 6, "cost": lm_costs.flash_bwd(
        1, 20, t, 256, 2)}
    assert k["flash_fwd"]["cost"]["flops"] == 20 * 4 * 256 * pairs
    assert k["moe_gmm"]["layers"] == 5
    assert k["moe_gmm"]["per_pair"]["flops"] == 18 * h * 1536
    assert k["moe_gmm"]["weights_per_layer_step"]["bytes"] == (
        3 * 8 * 3 * h * 1536 * 2)
    # the flash pair at its roofline on a v5e: FLOPs bind
    line = costs.roofline(k["flash_fwd"]["cost"], peaks.peaks_for("TPU v5 lite"))
    assert line["bound"] == "flops"
    values = glm_costs.reader_values(config, 1, t)
    assert values["moe_axes"] == {
        "tokens": t, "per_token": 4, "total": 64, "held": 8, "hidden": h,
        "width": 1536, "rows": 32768}
    assert values["moe_pairs_in_trace"] is None  # no fence noted here
    # without the module (a weight of 0) the costs are the five layers'
    bare = {**config, "model": {**config["model"], "kwargs": {
        **config["model"]["kwargs"], "mtp_weight": 0.0}}}
    less = glm_costs.step_flops(bare, 1, t)
    assert less["mtp"] == 0 and less["total"] == flops["total"] - flops["mtp"]
    assert glm_costs.kernels(bare, 1, t)["flash_fwd"]["layers"] == 5


def test_the_costs_are_the_programs_own_count():
    """``estimator.mfu`` (the costs module's total) and
    ``estimator.mfu_program`` (``fit_facts``' ``flops_per_row``) divide the
    same number."""
    pytest.importorskip("jax")
    from raydp_tpu.models import LatentMTPHybridLM

    config = cells.resolve(ROOT, CELL).config
    module = LatentMTPHybridLM.from_config(config, **config["model"]["kwargs"])
    parts = module.flops_per_row_parts(8192)
    flops = glm_costs.step_flops(config, 1, 8192)
    assert sum(parts.values()) == flops["total"]
    for key in ("layers", "experts", "attention", "head"):
        assert parts[key] == flops[key], key


LAYOUT = "{1,0:T(8,128)(2,1)S(1)}"


def _line(name, result):
    return (f"%{name} = {result}{LAYOUT} fusion(bf16[8,16]{LAYOUT} %p.1), "
            "kind=kLoop")


STEP = {
    "fusion.1": {"result": "bf16[8,16]", "scopes": [
        "loss_and_grad", "hybridlm.attention", "hybridlm.attention.query"]},
    "fusion.2": {"result": "f32[8,16]", "scopes": [
        "loss_and_grad", "hybridlm.attention", "hybridlm.attention.latent"]},
    "fusion.3": {"result": "f32[16,8]", "scopes": [
        "loss_and_grad", "hybridlm.attention"]},
    "fusion.4": {"result": "bf16[16,8]", "scopes": [
        "loss_and_grad", "hybridlm.mtp", "hybridlm.mtp.combine"]},
    "fusion.5": {"result": "bf16[4,8]", "scopes": [
        "loss_and_grad", "hybridlm.mtp", "hybridlm.attention",
        "hybridlm.attention.query"]},
    "fusion.6": {"result": "f32[4,8]", "scopes": [
        "loss_and_grad", "hybridlm.mtp", "hybridlm.mtp.loss"]},
    "fusion.7": {"result": "f32[2,8]", "scopes": [
        "loss_and_grad", "hybridlm.loss"]},
    "fusion.8": {"result": "f32[2,4]", "scopes": ["optimizer_update"]},
}
OPS = {
    _line("fusion.1", "bf16[8,16]"): (15, 0.030),
    _line("fusion.2", "f32[8,16]"): (15, 0.012),
    _line("fusion.3", "f32[16,8]"): (9, 0.006),
    _line("fusion.4", "bf16[16,8]"): (9, 0.0045),
    _line("fusion.5", "bf16[4,8]"): (9, 0.009),
    _line("fusion.6", "f32[4,8]"): (9, 0.018),
    _line("fusion.7", "f32[2,8]"): (9, 0.027),
    _line("fusion.8", "f32[2,4]"): (9, 0.036),
}
NEW = ("model.mtp_scope_ms", "model.latent_proj_scope_ms")


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
    # the module: its combine, its block's part and its loss
    assert _read("model.mtp_scope_ms", sources) == pytest.approx(
        1e3 * (0.0045 + 0.009 + 0.018) / 9)
    # the projections, the module's block's among them; not the rest of the
    # attention's scope
    assert _read("model.latent_proj_scope_ms", sources) == pytest.approx(
        1e3 * (0.030 + 0.012 + 0.009) / 9)
    assert _read("model.attention_scope_ms", sources) == pytest.approx(
        1e3 * (0.030 + 0.012 + 0.006 + 0.009) / 9)
    # the main head's loss stays the main head's
    assert _read("model.loss_scope_ms", sources) == pytest.approx(
        1e3 * 0.027 / 9)


def test_a_program_with_the_latent_scope_alone_gives_that_part(
        sources, monkeypatch):
    """The parent commit's Ling program: ``hybridlm.attention.latent`` and no
    ``.query``."""
    from raydp_tpu.obs import profiler

    monkeypatch.setattr(profiler, "device_scopes", lambda: {"3#1": {
        k: v for k, v in STEP.items() if k in ("fusion.2", "fusion.3")}})
    assert _read("model.latent_proj_scope_ms", sources) == pytest.approx(
        1e3 * 0.012 / 9)
    assert _read("model.mtp_scope_ms", sources) is None


@pytest.mark.parametrize("name", NEW)
def test_a_new_reader_gives_nothing_where_there_is_nothing_to_read(
        name, sources, monkeypatch):
    from raydp_tpu.obs import profiler

    assert _read(name, {"values": {"steps_in_trace": 9}}) is None  # no trace
    assert _read(name, {**sources, "values": {}}) is None  # no count
    monkeypatch.setattr(scopes, "_made", [])
    monkeypatch.setattr(profiler, "device_scopes", lambda: {
        "3#1": {"fusion.8": STEP["fusion.8"]}}, raising=False)
    assert _read(name, sources) is None  # a program without such a scope
    monkeypatch.setattr(scopes, "_made", [])
    monkeypatch.delattr(profiler, "device_scopes")  # a program without a map
    assert _read(name, sources) is None


def test_the_references_copy_imports_nothing_from_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "glm_moe_lite.py")) as f:
        text = f.read()
    assert "import raydp_tpu" not in text and "from raydp_tpu" not in text
    # a full softmax a head, every held expert on every token, the whole
    # logits of a block: no kernel, no sort, no chunked loss of the program's
    for name in ("ragged_dot", "gmm", "lax.sort", "argsort", "pallas",
                 "custom_vjp", "flash"):
        assert name not in text, name
    assert 'default_matmul_precision("highest")' in text


def test_rehearsal_runs_every_phase_of_the_cell():
    # one CPU device, as a run has (a test process may ask for eight); its
    # own seed, so that no other test's run writes the same log; and a window
    # of 10 s: how many epoch fences fall inside one is the machine's load
    # (under a suite's workers a 2-s window held fewer than the two a run
    # needs: tests/test_delta_bench.py, PR 52's run)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 53), "--seconds", "10",
         "--trace", "1", "--rehearse-on-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["metrics"] == {} and last["failed"] == 0 and last["attempted"] > 0
    for part in ("a_arithmetic", "b_data", "c_fit_trains", "d_window"):
        assert f"correct[{part}] = True" in proc.stdout
    assert "pairs dropped 0 (must be 0)" in proc.stdout
    assert "by layer [" in proc.stdout  # five expert layers: the module's last
    assert proc.stdout.count("token_loss_rms") >= 2  # as run and matched


def test_a_program_without_the_model_leaves_at_once(tmp_path):
    """The parent commit's ``raydp_tpu`` has ``HybridLM`` and no
    ``LatentMTPHybridLM``: the phase leaves before it starts a cluster, with
    a message, and the run prints no result."""
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
    assert "cannot run configuration 'glm-4.7-flash'" in proc.stdout
    assert "LatentMTPHybridLM" in proc.stdout
    assert '"correct"' not in proc.stdout and "init_etl" not in proc.stdout
