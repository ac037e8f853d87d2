"""What ISSUE 45 adds to the benchmark, held on the CPU: the cell resolves by
its names to the files beside the others'; the configuration file carries
every published number but the six it lists as reduced; the two new readers
read the program's own scope and give nothing where there is nothing to read
(a parent commit); the reference's AdamW in blocks is Granite's bit for bit;
a program without the model leaves at once; the rehearsal runs every phase."""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import cells, costs, delta_costs, layers, peaks, scopes  # noqa: E402

CELL = "olmo-hybrid-7b.pretrain-8k-delta"
METRICS = os.path.join(ROOT, "benchmark", "layer_metrics")
PEAKS = peaks.peaks_for("TPU v5 lite")
# the catalog's row (model-configs guide), its numbers and flags
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "linear_num_key_heads": 30,
    "linear_num_value_heads": 30, "linear_key_head_dim": 96,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None}}


def test_the_cell_resolves_with_its_driver_readers_and_traffic():
    cell = cells.resolve(ROOT, CELL)
    assert cell.kind == "lmpretrain" and cell.chips == 1
    assert cell.driver_path.endswith("drivers/lmpretrain.py")
    names = {m["name"] for m in cell.per_layer}
    assert {"model.delta_scope_ms", "kernel.delta_rule_roofline",
            "estimator.mfu", "kernel.flash_fwd_roofline",
            "kernel.flash_bwd_roofline", "model.attention_scope_ms",
            "device.scope_unattributed_share"} <= names
    # PR 45's 18 and, since PR 49, the mixer's scope outside the scan;
    # beside them, since PR 55, the compile account's nine of every cell
    account = {n for n in names if n != "estimator.compile_s" and n.startswith(
        ("estimator.compile_", "estimator.fit_unaccounted_s"))}
    assert len(account) == 9
    assert len(names - account) == 19 and "model.delta_mixer_scope_ms" in names
    assert {m["name"] for m in cell.end_to_end} == {"fit_samples_per_s", "setup_s"}
    t = cell.traffic
    assert (t["seq_len"], t["batch"], t["held_out_rows"], t["zipf_a"],
            t["bigram_tilt"], t["streaming"]) == (8192, 1, 1, 1.1, 0.5, False)
    assert t["train_rows"] in (2, 3) and t["epoch_why"]
    model = cell.config["model"]
    assert model["class"] == "raydp_tpu.models.DeltaHybridLM"
    assert model["reference"] == "benchmark.reference.olmo_hybrid"
    assert model["costs"] == "benchmark.harness.delta_costs"


def test_the_benchmark_only_grew_at_its_ends():
    """The six cells, five configurations and the metrics the benchmark had
    are its first, in their order; PR 45's are after them (and a later PR's
    after those); a metric's list of cells gained PR 45's cell after the
    cells it had and nothing else."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]][:5] == [
        "dlrm-criteo-kaggle", "ouro-2.6b", "granite-4.0-h-micro",
        "lfm2-8b-a1b", "smallthinker-21b-a3b"]
    assert [c["name"] for c in bench["configs"]][5:6] == ["olmo-hybrid-7b"]
    earlier = [w["name"] for w in bench["workloads"]][:6]
    assert [w["name"] for w in bench["workloads"]][6:7] == [CELL]
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("model.delta_scope_ms")
    assert names[at:at + 2] == ["model.delta_scope_ms",
                                "kernel.delta_rule_roofline"]
    assert "model.attention_scope_ms" in names[:at]
    for metric in bench["end_to_end"] + bench["per_layer"][:at + 2]:
        cells_ = metric.get("workloads", [])
        if CELL in cells_:
            assert set(cells_[:cells_.index(CELL)]) <= set(earlier), (
                metric["name"])
    new = {m["name"]: m for m in bench["per_layer"][at:at + 2]}
    assert all(m["workloads"][:1] == [CELL] and m["source"] == "device_trace"
               and m["moves"] == "fit_samples_per_s" for m in new.values())
    assert new["kernel.delta_rule_roofline"]["unit"] == "%"
    assert bench["run_seconds"] == 20


@pytest.mark.parametrize("key", sorted(PUBLISHED))
def test_every_published_number_is_in_the_configuration_file(key):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    reduced = {"num_hidden_layers": 4, "num_attention_heads": 15,
               "num_key_value_heads": 15, "linear_num_key_heads": 15,
               "linear_num_value_heads": 15, "vocab_size": 12544}
    assert sorted(config["reduced"]) == sorted(reduced)
    if key in reduced:
        assert config[key] == reduced[key]
        assert config["published"][key] == PUBLISHED[key]
    else:
        assert config[key] == PUBLISHED[key]
    # the pattern whole: three linear-attention layers to one full
    assert config["layer_types"] == (["linear_attention"] * 3
                                     + ["full_attention"]) * 8
    assert config["head_dim"] * PUBLISHED["num_attention_heads"] == 3840


def test_costs_match_a_hand_count():
    """The roofline's least time at the cell's sizes: bytes bind, 0.173 ms
    forward and 0.289 ms backward a layer."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    kernels = delta_costs.kernels(config, 1, 8192)
    fwd, bwd = kernels["delta_fwd"]["cost"], kernels["delta_bwd"]["cost"]
    assert fwd == {"flops": 8192 * 15 * 6 * 96 * 192,
                   "bytes": 8192 * 15 * 578 * 2}
    assert bwd == {"flops": 2 * fwd["flops"], "bytes": 8192 * 15 * 964 * 2}
    for cost, ms in ((fwd, 0.1734), (bwd, 0.2893)):
        least = costs.roofline(cost, PEAKS)
        assert least["bound"] == "bytes"
        assert least["min_s"] * 1e3 == pytest.approx(ms, rel=1e-3)
    flash = kernels["flash_fwd"]["cost"]
    assert flash["flops"] == 15 * 4 * 128 * (8192 * 8193 // 2)
    parts = delta_costs.step_flops(config, 1, 8192)
    assert parts["total"] == pytest.approx(3.5e13, rel=0.05)
    assert parts["delta"] / parts["total"] < 0.005  # the scan is bytes, not FLOPs


# -- the two readers -----------------------------------------------------------

LAYOUT = "{1,0:T(8,128)(2,1)}"


def _line(name, result):
    return f"%{name} = {result}{LAYOUT} fusion(bf16[8,16]{LAYOUT} %p.1), kind=kLoop"


PROGRAM = {
    "fusion.1": {"result": "f32[8,16]", "scopes": [
        "loss_and_grad", "hybridlm.delta", "delta_rule"]},
    "fusion.2": {"result": "bf16[8,16]", "scopes": [
        "loss_and_grad", "hybridlm.delta"]},  # a projection: not the scan
    "fusion.3": {"result": "bf16[8,16]", "scopes": [
        "loss_and_grad", "hybridlm.mlp"]},
}
OPS = {_line("fusion.1", "f32[8,16]"): (27, 0.540),
       _line("fusion.2", "bf16[8,16]"): (27, 0.100),
       _line("fusion.3", "bf16[8,16]"): (27, 1.000)}


def _sources(monkeypatch, programs, kernels=True, steps=9):
    from raydp_tpu.obs import profiler

    monkeypatch.setattr(profiler, "device_scopes", lambda: programs)
    monkeypatch.setattr(scopes, "_made", [])
    monkeypatch.setattr(scopes, "_printed", True)
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b.json")) as f:
        config = json.load(f)
    return {"values": {"steps_in_trace": steps},
            "trace": types.SimpleNamespace(ops=OPS), "peaks": PEAKS,
            "kernels": delta_costs.kernels(config, 1, 8192) if kernels else {}}


def _read(name, sources):
    return layers.read_metric(os.path.join(METRICS, name + ".py"), sources)


def test_the_readers_read_the_scope_and_not_its_surroundings(monkeypatch):
    src = _sources(monkeypatch, {"3#1": PROGRAM})
    assert _read("model.delta_scope_ms", src) == pytest.approx(60.0)
    least = sum(costs.roofline(src["kernels"][k]["cost"], PEAKS)["min_s"]
                for k in ("delta_fwd", "delta_bwd"))
    share = _read("kernel.delta_rule_roofline", src)
    assert share == pytest.approx(100 * 9 * 3 * least / 0.540)
    assert 0 < share < 100


@pytest.mark.parametrize("what", ["no_scope", "no_map", "no_trace",
                                  "no_steps", "no_costs"])
def test_the_readers_give_none_where_there_is_nothing_to_read(monkeypatch, what):
    """A parent commit's program names no ``delta_rule`` scope (or gives no
    map at all): the readers return None and do not raise, and the line
    leaves the metrics out."""
    programs = {"3#1": PROGRAM}
    if what == "no_scope":
        programs = {"3#1": {k: {**v, "scopes": [
            s for s in v["scopes"] if s != "delta_rule"]}
            for k, v in PROGRAM.items()}}
    elif what == "no_map":
        programs = {}
    src = _sources(monkeypatch, programs, kernels=what != "no_costs",
                   steps=0 if what == "no_steps" else 9)
    if what == "no_trace":
        src["trace"] = None
    assert _read("kernel.delta_rule_roofline", src) is None
    if what != "no_costs":
        assert _read("model.delta_scope_ms", src) is None


# -- the reference ---------------------------------------------------------------


def test_the_references_copy_imports_nothing_from_the_program():
    path = os.path.join(ROOT, "benchmark", "reference", "olmo_hybrid.py")
    with open(path) as f:
        text = f.read()
    assert "import raydp_tpu" not in text and "from raydp_tpu" not in text
    assert "triangular" not in text.split('"""', 2)[2]  # no solve: per token
    assert 'default_matmul_precision("highest")' in text


@pytest.mark.parametrize("block", [1000, 1 << 22])
def test_the_references_adamw_in_blocks_is_granites_bit_for_bit(
        block, monkeypatch):
    from benchmark.reference import granite_hybrid, olmo_hybrid

    monkeypatch.setattr(olmo_hybrid, "ADAMW_BLOCK", block)
    rng = np.random.default_rng(0)
    shapes = [(70, 50), (7,), (3, 11, 13), (4097,), (1, 5), (90, 40)]
    ours = [rng.standard_normal(s).astype(np.float32) for s in shapes]
    # a leaf fetched from the chip may lie column-major, its copies with it
    ours[-1] = np.asfortranarray(ours[-1])
    theirs = [a.copy() for a in ours]
    state, state_ref = olmo_hybrid.adamw_init(ours), granite_hybrid.adamw_init(theirs)
    hyper = (3e-4, 0.9, 0.95, 0.1)
    for _ in range(3):
        grads = [rng.standard_normal(s).astype(np.float32) for s in shapes]
        olmo_hybrid.adamw_step(ours, grads, state, *hyper)
        granite_hybrid.adamw_step(theirs, [g.copy() for g in grads],
                                  state_ref, *hyper)
    assert state["count"] == 3
    for got, want in zip(ours + state["m"] + state["v"],
                         theirs + state_ref["m"] + state_ref["v"]):
        assert np.array_equal(got, want)
    assert not ours[-1].flags.c_contiguous and state["m"][-1].flags.f_contiguous


# -- the cell end to end ---------------------------------------------------------


def test_a_program_without_the_model_leaves_at_once(tmp_path):
    """The parent commit's ``raydp_tpu.models`` has no ``DeltaHybridLM``:
    with this PR's benchmark files laid over it the phase leaves before it
    starts a cluster, with a message, another exit code than 0, and no
    result."""
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
    assert "cannot run configuration 'olmo-hybrid-7b'" in proc.stdout
    assert '"correct"' not in proc.stdout and "init_etl" not in proc.stdout


def test_rehearsal_runs_every_phase_of_the_cell():
    # one CPU device, as a run has: tests/conftest.py asks for eight, and a
    # batch of 2 over 8 devices is an epoch of no steps
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    # a window of 10 s, not 2: the run fails, by design, where fewer than two
    # epoch fences fall inside its window, and how many do is the machine's
    # load and nothing of the cell (29 epochs in 2 s alone on this sandbox,
    # 4 beside 24 busy processes, fewer than 2 under the suite's six workers
    # in the driver's run of PR 52: "fewer than two epoch fences in a window
    # of 2.0 s"). Every check below is what it was
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2 ** 31 + 45), "--seconds", "10",
         "--trace", "1", "--rehearse-on-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["metrics"] == {} and last["failed"] == 0 and last["attempted"] > 0
    for part in ("a_arithmetic", "b_data", "c_fit_trains", "d_window"):
        assert f"correct[{part}] = True" in proc.stdout
