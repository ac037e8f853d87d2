"""The window-and-global routed cell's files (PR 42): the cell resolves, the
configuration carries every published number, ``window_costs`` against a hand
count, the window readers on a made-up trace (and None where there is nothing
to read, as from a parent program), the reference's independence of the
program, the cell's rehearsal through every phase, and a program without the
model."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import (  # noqa: E402
    cells, costs, layers, lm_costs, peaks, window_costs, xplane)

CELL = "smallthinker-21b-a3b.pretrain-16k-window"
METRICS = os.path.join(ROOT, "benchmark", "layer_metrics")
PEAKS = peaks.peaks_for("TPU v5 lite")
NEW = ("kernel.flash_window_fwd_roofline", "kernel.flash_window_bwd_roofline",
       "model.attention_scope_ms")


def test_the_cell_resolves_with_its_driver_readers_and_traffic():
    cell = cells.resolve(ROOT, CELL)
    assert cell.kind == "lmpretrain_routed_placed" and cell.chips == 1
    assert cell.driver_path.endswith("drivers/lmpretrain_routed_placed.py")
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    assert {"kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
            "kernel.moe_gmm_roofline", "model.experts_scope_ms",
            "model.loss_scope_ms", "model.moe_load_max_over_mean",
            "model.moe_likely_bound_share", "device.lm_step_ms",
            "estimator.mfu", "device.scope_unattributed_share"} <= names
    # the readers that find operations by shapes misread here: not listed
    assert not names & {"model.moe_ms", "model.exit_loss_ms", "model.ssd_ms"}
    assert {m["name"] for m in cell.end_to_end} == {"fit_samples_per_s", "setup_s"}
    t = cell.traffic
    assert (t["seq_len"], t["batch"], t["train_rows"], t["held_out_rows"],
            t["zipf_a"], t["bigram_tilt"], t["streaming"]) == (
        16384, 2, 4, 2, 1.1, 0.5, False)
    for mode in ("as_run", "matched"):
        assert set(t["selection_tolerance"][mode]) == {
            "differ_share_max", "margin_max"}
        assert set(t["arith_tolerance"][mode]) == {
            "loss_abs", "logits_rel", "grads_rel", "token_loss_rms"}
    model = cell.config["model"]
    assert model["class"] == "raydp_tpu.models.RoutedHybridLM"
    assert model["kwargs"]["embed_std"] == 1.0
    assert cell.config["model_type"] == "smallthinker"
    assert model["reference"] == "benchmark.reference.smallthinker"
    assert model["costs"] == "benchmark.harness.window_costs"
    small = cells.sized(cell.config, rehearsal=True)
    assert (small["hidden_size"], small["head_dim"], small["sliding_window_size"],
            small["moe_num_primary_experts"], small["share"]["experts_total"],
            small["vocab_size"]) == (64, 16, 8, 2, 8, 256)


def test_the_new_entries_are_the_benchmarks_last():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["configs"][-1]["name"] == "smallthinker-21b-a3b"
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["workloads"][-1]["chips"] == 1
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW)
    for metric in bench["per_layer"][-3:]:
        assert metric["workloads"] == [CELL]
    for metric in bench["per_layer"] + bench["end_to_end"]:
        if CELL in metric.get("workloads", ()):
            assert metric["workloads"][-1] == CELL, metric["name"]
    assert all(len(w["why"]) <= 200 for w in bench["workloads"] + bench["configs"])


def test_every_published_number_is_in_the_configuration_file():
    c = cells.resolve(ROOT, CELL).config
    assert (c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"],
            c["head_dim"], c["moe_ffn_hidden_size"],
            c["moe_num_active_primary_experts"], c["sliding_window_size"],
            c["rope_theta"], c["rms_norm_eps"], c["max_position_embeddings"]
            ) == (2560, 28, 4, 128, 768, 6, 4096, 1500000, 1e-6, 16384)
    assert c["sliding_window_layout"] == c["rope_layout"] == [0, 1, 1, 1] * 13
    assert (c["num_hidden_layers"], c["moe_num_primary_experts"],
            c["vocab_size"]) == (4, 16, 37984)
    assert c["reduced"] == ["num_hidden_layers", "moe_num_primary_experts",
                            "vocab_size"]
    assert c["published"] == {"num_hidden_layers": 52,
                              "moe_num_primary_experts": 64,
                              "vocab_size": 151936}
    assert c["share"]["experts_total"] == 64 and c["share"]["chips_per_layer"] == 4
    assert (c["share"]["first_layer"], c["share"]["first_expert"]) == (0, 0)
    assert "4 chips" in c["stands_for"] and c["assumed"]["router_input"]
    assert any("secondary" in d for d in c["departures_from_source"])
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(e for e in json.load(f)["configs"]
                     if e["name"] == "smallthinker-21b-a3b")
    assert entry["reduced"] == c["reduced"] and entry["source"] == c["source"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        return
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert c["source"] == row["source_url"]
    for key, value in row["config"].items():
        assert c["published"].get(key, c[key]) == value, key
        assert (key in c["reduced"]) == (c[key] != value), key


def test_costs_match_a_hand_count():
    c = cells.resolve(ROOT, CELL).config
    t, w = 16384, 4096
    assert window_costs.window_pairs(t, w) == 58_722_304
    assert window_costs.window_pairs(t, t) == t * (t + 1) // 2 == 134_225_920
    assert window_costs.window_pairs(100, 4096) == 5050
    # the pairs, query by query
    assert window_costs.window_pairs(50, 8) == sum(
        min(i + 1, 8) for i in range(50))
    flops = window_costs.step_flops(c, 2, t)
    attention = 2560 * 3584 * 2 + 2 * 2560 * 512
    assert attention == 20_971_520
    assert flops["layers"] == 6 * 4 * (attention + 2560 * 64) * 32768
    assert flops["experts"] == 4 * 18 * 2560 * 768 * (32768 * 6 * 16 // 64)
    assert flops["attention"] == 12 * 3584 * 2 * (
        134_225_920 + 3 * 58_722_304)
    assert flops["head"] == 6 * 2560 * 37984 * 32768
    assert flops["total"] == sum(
        flops[k] for k in ("layers", "experts", "attention", "head"))
    k = window_costs.kernels(c, 2, t)
    assert k["flash_fwd"]["cost"] == lm_costs.flash_fwd(2, 28, t, 128, 2)
    assert k["flash_window_fwd"]["cost"] == {
        "flops": 56 * 4 * 128 * 58_722_304,
        "bytes": k["flash_fwd"]["cost"]["bytes"]}
    assert k["flash_window_bwd"]["cost"]["flops"] == 2 * k[
        "flash_window_fwd"]["cost"]["flops"]
    # a window call needs 43.7 % of the causal call's operations
    assert 0.437 < (k["flash_window_fwd"]["cost"]["flops"]
                    / k["flash_fwd"]["cost"]["flops"]) < 0.438
    assert k["moe_gmm"]["layers"] == 4
    assert k["moe_gmm"]["per_pair"]["flops"] == 18 * 2560 * 768


def _trace(ops):
    return xplane.TraceSummary(
        window_s=1.0, busy_s=sum(s for _, s in ops.values()), devices=1,
        ops=ops, device_ops=[], idle_gaps=[])


def test_the_window_readers_read_the_window_calls_alone():
    c = cells.resolve(ROOT, CELL).config
    kernels = window_costs.kernels(c, 2, 16384)
    least = {name: costs.roofline(k["cost"], PEAKS)["min_s"]
             for name, k in kernels.items() if "cost" in k}
    line = " = (bf16[56,16384,128]) custom-call(), custom_call_target=\"tpu_custom_call\""
    ops = {
        "%flash_attention_fwd.1" + line: (2, 0.100),
        "%jvp_flash_attention_window_fwd_.3" + line: (6, 0.120),
        "%transpose_jvp_flash_attention_bwd_dq__.1" + line: (2, 0.080),
        "%transpose_jvp_flash_attention_bwd_dkv__.1" + line: (2, 0.120),
        "%transpose_jvp_flash_attention_window_bwd_dq__.5" + line: (6, 0.150),
        "%transpose_jvp_flash_attention_window_bwd_dkv__.5" + line: (6, 0.210),
    }
    sources = {"trace": _trace(ops), "kernels": kernels, "peaks": PEAKS,
               "values": {}}

    def read(name):
        for ext in (".json", ".py"):
            if os.path.isfile(os.path.join(METRICS, name + ext)):
                return layers.read_metric(os.path.join(METRICS, name + ext),
                                          sources)

    assert read("kernel.flash_window_fwd_roofline") == pytest.approx(
        100 * 6 * least["flash_window_fwd"] / 0.120)
    assert read("kernel.flash_window_bwd_roofline") == pytest.approx(
        100 * 6 * least["flash_window_bwd"] / 0.360)
    # the accepted readers see the global layer's calls alone
    assert read("kernel.flash_fwd_roofline") == pytest.approx(
        100 * 2 * least["flash_fwd"] / 0.100)
    assert read("kernel.flash_bwd_roofline") == pytest.approx(
        100 * 2 * least["flash_bwd"] / 0.200)
    for name in ("kernel.flash_window_fwd_roofline",
                 "kernel.flash_window_bwd_roofline"):
        assert 0 < read(name) < 100


def test_the_new_readers_give_none_where_there_is_nothing_to_read():
    """A parent program has no window call, no such cost and no scope map;
    an untraced run has no trace."""
    c = cells.resolve(ROOT, CELL).config
    kernels = window_costs.kernels(c, 2, 16384)
    causal_only = {"%flash_attention_fwd.1 = bf16[1] custom-call()": (2, 0.1)}
    for sources in (
            {"trace": None, "kernels": kernels, "peaks": PEAKS, "values": {}},
            {"trace": _trace(causal_only), "kernels": kernels, "peaks": PEAKS,
             "values": {}},
            {"trace": _trace(causal_only), "kernels": {}, "peaks": PEAKS,
             "values": {"steps_in_trace": 0}}):
        for name in NEW:
            path = next(os.path.join(METRICS, name + ext)
                        for ext in (".json", ".py")
                        if os.path.isfile(os.path.join(METRICS, name + ext)))
            assert layers.read_metric(path, sources) is None, name


def test_the_references_copy_imports_nothing_from_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "smallthinker.py")) as f:
        text = f.read()
    assert "import raydp_tpu" not in text and "from raydp_tpu" not in text
    for name in ("ragged_dot", "gmm", "lax.sort", "argsort", "pallas_call",
                 "flash_attention"):
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
    assert "matched, the fit's first batch of 2 x 32 tokens. (i) selection: 0 of 256" in proc.stdout


def test_a_program_that_cannot_place_experts_leaves_at_once(tmp_path):
    """The parent commit's ``raydp_tpu`` has ``RoutedHybridLM`` without
    ``placed_by_load`` (and builds no such family): under the benchmark's
    files laid over it, the phase leaves before it starts a cluster, with a
    message, and the run prints no result."""
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    package = tmp_path / "raydp_tpu"
    (package / "models").mkdir(parents=True)
    (package / "__init__.py").write_text("")
    (package / "models" / "__init__.py").write_text(
        "class HybridLM: pass\nclass RoutedHybridLM(HybridLM): pass\n")
    env = dict(os.environ, PYTHONPATH=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "benchmark" / "run.py"), "--workload",
         CELL, "--rehearse-on-cpu"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "cannot run configuration 'smallthinker-21b-a3b'" in proc.stdout
    assert "places no experts" in proc.stdout
    assert '"correct"' not in proc.stdout and "init_etl" not in proc.stdout


def test_the_fourth_gap_parts_a_bf16_model_from_a_float32_one():
    """``token_loss_rms`` on made-up states: the same state through a
    float32 head and loss reads 0; through the reference's bf16 logits and
    loss it reads the rounding of a loss token by token, which a mean over
    the tokens hides."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark.drivers import lmpretrain_routed_placed as placed
    from benchmark.reference import smallthinker as ref

    cfg = {"norm_eps": 1e-6}
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    params = {"params": {"head": 0.02 * jax.random.normal(key[0], (64, 4096))}}
    hidden = jax.random.normal(key[1], (2, 48, 64))
    targets = jax.random.randint(key[2], (2, 48), 0, 4096)
    exact = placed.token_losses(
        placed.reference_token_losses(ref, cfg, jnp.float32), params, hidden,
        targets, 32)
    assert exact.shape == (96,) and exact.dtype == np.float32
    want = -jnp.take_along_axis(jax.nn.log_softmax(
        hidden @ params["params"]["head"], axis=-1), targets[..., None],
        axis=-1)[..., 0]
    np.testing.assert_allclose(exact, np.asarray(want).ravel(), atol=2e-6)
    low = placed.token_losses(
        placed.reference_token_losses(ref, cfg, jnp.bfloat16), params, hidden,
        targets, 32)
    token_by_token = placed._rms(low, exact)
    assert placed._rms(exact, exact) == 0.0
    # a loss near log(4096) = 8.3 rounds to bf16 in steps of 1/32
    assert 2e-3 < token_by_token < 3e-2
    assert abs(float(low.mean()) - float(exact.mean())) < token_by_token / 3
