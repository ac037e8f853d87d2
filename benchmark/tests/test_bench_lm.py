"""The ``lmfit`` kind's files (PR 27): the cell resolves, ``lm_costs`` against
hand-worked numbers, the token generator, the new per-layer readers on a
synthetic trace summary and registry (with and without what they read), and
the plain reference's independence of the program."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, layers, lm_costs, peaks, tokens, xplane  # noqa: E402

CELL = "ouro-2.6b.pretrain-4k"
METRICS = os.path.join(ROOT, "benchmark", "layer_metrics")
PEAKS = peaks.peaks_for("TPU v5 lite")


def test_the_cell_resolves_with_its_readers_and_its_published_widths():
    cell = cells.resolve(ROOT, CELL)
    assert cell.kind == "lmfit" and cell.chips == 1
    assert cell.driver_path.endswith("drivers/lmfit.py")
    names = {m["name"] for m in cell.per_layer}
    assert {"kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline",
            "model.exit_loss_ms", "device.lm_step_ms",
            "estimator.tok_s_program", "estimator.mfu", "estimator.mfu_program",
            "etl.query_s",
            "exchange.stage_s", "estimator.compile_s", "estimator.dispatch_ms",
            "estimator.restart_ms", "device.idle_share.fit"} == names
    assert {m["name"] for m in cell.end_to_end} == {"fit_samples_per_s", "setup_s"}
    c = cell.config
    assert (c["hidden_size"], c["num_attention_heads"], c["head_dim"],
            c["intermediate_size"], c["vocab_size"], c["total_ut_steps"]) == (
        2048, 16, 128, 5632, 49152, 4)
    assert c["num_hidden_layers"] == 6 and c["reduced"] == ["num_hidden_layers"]
    assert c["published"]["num_hidden_layers"] == 48
    t = cell.traffic
    assert (t["seq_len"], t["batch"], t["train_rows"], t["held_out_rows"],
            t["streaming"]) == (4096, 2, 4, 2, False)
    small = cells.sized(cell.config, rehearsal=True)
    assert small["hidden_size"] == small["num_attention_heads"] * small["head_dim"]
    assert small["model"]["attn_impl"] == "flash"  # one level deep: merged


def test_entries_the_benchmark_had_are_where_they_were():
    """What ``test_bench_program_readers.py`` pinned by count, as a rule: the
    13 per-layer metrics and the 2 cells the benchmark had before this PR
    are its first 13 and first 2, in their order, PR 24's five last among
    them; a list of cells only ever grew at its end."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    had = ["etl.query_s", "exchange.ingest_ms", "exchange.h2d_ms",
           "estimator.table_update_ms", "estimator.mfu",
           "kernel.interaction_roofline", "device.step_ms",
           "device.idle_share.fit", "exchange.stage_s", "estimator.compile_s",
           "estimator.dispatch_ms", "estimator.restart_ms",
           "estimator.mfu_program"]
    assert [m["name"] for m in bench["per_layer"][:13]] == had
    assert bench["per_layer"][12]["unit"] == "%"
    cells_had = ["dlrm-criteo-kaggle.etl-stream", "dlrm-criteo-kaggle.fit-resident"]
    assert [w["name"] for w in bench["workloads"][:2]] == cells_had
    for m in bench["per_layer"][:13] + bench["end_to_end"]:
        listed = m.get("workloads")
        if listed is not None:
            old = [w for w in listed if w in cells_had]
            assert listed[:len(old)] == old, m["name"]  # new cells at the end
    assert [w["name"] for w in bench["workloads"][2:]] == [CELL]


def test_every_published_number_is_in_the_configuration_file():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        entry = next(e for e in map(json.loads, f) if e["name"] == "Ouro-2.6B")
    config = cells.resolve(ROOT, CELL).config
    differs = [k for k, v in entry["config"].items() if config.get(k) != v]
    assert differs == ["num_hidden_layers"]


def test_step_flops_against_hand_worked_numbers():
    # ISSUE 27's arithmetic: one layer 4 x 2048^2 + 3 x 2048 x 5632 = 51.4 M
    assert lm_costs.layer_params(2048, 5632) == 51_380_224
    got = lm_costs.looplm_step_flops(2, 4096, 2048, 5632, 6, 4, 49152)
    assert got["layers"] == 6 * 51_380_224 * 8192 * 24  # 6.06e13
    assert got["heads"] == 6 * 2048 * 49152 * 8192 * 4  # 1.98e13
    # causal: T (T + 1) / 2 kept pairs a sequence, 4 x hidden forward, x 3
    assert got["attention"] == 12 * 2048 * (4096 * 4097 // 2) * 2 * 24
    assert got["total"] == pytest.approx(9.03e13, rel=2e-3)
    # a plain decoder is the loop count 1
    one = lm_costs.looplm_step_flops(2, 4096, 2048, 5632, 6, 1, 49152)
    assert one["total"] * 4 == got["total"]


@pytest.mark.parametrize("fn, per_pair, tensors", [
    (lm_costs.flash_fwd, 4, 4), (lm_costs.flash_bwd, 8, 7)])
def test_flash_costs_are_causal_and_count_each_tensor_once(fn, per_pair, tensors):
    cost = fn(2, 16, 4096, 128, 2)
    assert cost["flops"] == 32 * per_pair * 128 * (4096 * 4097 // 2)
    assert cost["bytes"] == 32 * (tensors * 4096 * 128 * 2 + 2 * 4096 * 4)
    # half of what a full T x T product would need, give or take the diagonal
    full = 32 * per_pair * 128 * 4096 * 4096
    assert 0.5 < cost["flops"] / full < 0.5002


def test_tokens_are_a_function_of_the_seed_and_carry_the_bigram_tilt():
    seed = 2 ** 31 + 11
    a = tokens.sequences(seed, 8, 512, 4096, 1.1, 0.5)
    b = tokens.sequences(seed, 8, 512, 4096, 1.1, 0.5)
    other = tokens.sequences(seed + 1, 8, 512, 4096, 1.1, 0.5)
    assert a.dtype == np.int32 and a.shape == (8, 513)
    assert np.array_equal(a, b) and not np.array_equal(a, other)
    assert a.min() >= 0 and a.max() < 4096
    # the most frequent successor of a token follows it about half the time
    prev, nxt = a[:, :-1].ravel(), a[:, 1:].ravel()
    top = np.bincount(prev).argmax()
    after = nxt[prev == top]
    assert np.bincount(after).max() / len(after) > 0.35
    # Zipf: the commonest id takes far more than a uniform share
    assert np.bincount(a.ravel()).max() / a.size > 20 / 4096
    table, ids = tokens.raw_frame(seed, 8, 512, 4096, 1.1, 0.5)
    assert np.array_equal(ids, a)
    assert str(table.schema.field("tokens").type) == "fixed_size_list<item: int32>[513]"


# -- the readers ---------------------------------------------------------------

FWD = ('%jvp_flash_attention_fwd_.3 = (bf16[32,4096,128]{2,1,0}, f32[32,4096,1]{2,1,0}, '
       'f32[32,4096,1]{2,1,0}) custom-call(%a, %b), custom_call_target="tpu_custom_call"')
DQ = ('%transpose_jvp_flash_attention_bwd_dq__.7 = bf16[32,4096,128]{2,1,0} custom-call(%a), '
      'custom_call_target="tpu_custom_call"')
DKV = ('%flash_attention_bwd_dkv.9 = (bf16[32,4096,128]{2,1,0}, bf16[32,4096,128]{2,1,0}) '
       'custom-call(%a), custom_call_target="tpu_custom_call"')
LOGITS = "%fusion.12 = f32[2048,49152]{1,0} fusion(%h, %w), kind=kOutput"
HEAD_GRAD = "%fusion.40 = f32[2048,49152]{1,0:T(8,128)} fusion(%h, %dz), kind=kOutput"
BACK = "%fusion.41 = bf16[2048,2048]{1,0} fusion(%dz, %w), kind=kOutput"


def summary(ops, busy_s=6.0, window_s=6.1):
    return xplane.TraceSummary(window_s=window_s, busy_s=busy_s, devices=1,
                               ops=ops, device_ops=[], idle_gaps=[])


def sources(ops, **values):
    return {"trace": summary(ops), "peaks": PEAKS, "values": values,
            "kernels": {"flash_fwd": {"cost": lm_costs.flash_fwd(2, 16, 4096, 128, 2)},
                        "flash_bwd": {"cost": lm_costs.flash_bwd(2, 16, 4096, 128, 2)}}}


def read(name, src):
    ext = ".py" if os.path.exists(os.path.join(METRICS, name + ".py")) else ".json"
    return layers.read_metric(os.path.join(METRICS, name + ext), src)


def test_flash_rooflines_find_the_named_calls():
    fwd_least = lm_costs.flash_fwd(2, 16, 4096, 128, 2)["flops"] / PEAKS["flops_per_s"]
    bwd_least = lm_costs.flash_bwd(2, 16, 4096, 128, 2)["flops"] / PEAKS["flops_per_s"]
    recomputed = FWD.replace("%jvp_flash_attention_fwd_.3", "%flash_attention_fwd.126")
    ops = {FWD: (24, 24 * 2 * fwd_least), recomputed: (24, 24 * 2 * fwd_least),
           DQ: (24, 24 * 1.5 * bwd_least),
           DKV: (24, 24 * 2.5 * bwd_least), LOGITS: (96, 1.0)}
    assert read("kernel.flash_fwd_roofline", sources(ops)) == pytest.approx(50.0)
    # one pass = one dq call + one dk/dv call, 4 x the least time together
    assert read("kernel.flash_bwd_roofline", sources(ops)) == pytest.approx(25.0)
    # a program that does not name its calls (the parent), or no trace
    unnamed = {FWD.replace("%jvp_flash_attention_fwd_", "%custom-call"): (48, 1.0)}
    for name in ("kernel.flash_fwd_roofline", "kernel.flash_bwd_roofline"):
        assert read(name, sources(unnamed)) is None
        assert read(name, {"trace": None}) is None


def test_exit_loss_and_step_time_divide_by_the_programs_own_step_count():
    ops = {LOGITS: (96, 0.9), HEAD_GRAD: (96, 0.6), BACK: (96, 0.5), FWD: (288, 0.7)}
    src = sources(ops, steps_in_trace=6, vocab_size=49152)
    # the logits and the head's gradient carry the axis; the product back to
    # the hidden state does not
    assert read("model.exit_loss_ms", src) == pytest.approx(1e3 * 1.5 / 6)
    assert read("device.lm_step_ms", src) == pytest.approx(1e3 * 6.0 / 6)
    # xplane.steps_traced would have said 96: the commonest call count
    assert xplane.steps_traced(ops) == 96
    for name in ("model.exit_loss_ms", "device.lm_step_ms"):
        assert read(name, sources(ops, vocab_size=49152)) is None  # no count
        assert read(name, {"trace": None, "values": {"steps_in_trace": 6}}) is None
    assert read("model.exit_loss_ms", sources(
        {BACK: (96, 0.5)}, steps_in_trace=6, vocab_size=49152)) is None


def test_tokens_per_second_reads_the_programs_gauge(monkeypatch):
    from raydp_tpu import obs

    monkeypatch.setattr(obs.metrics, "snapshot", lambda: {
        "estimator.tokens_per_sec": {"type": "gauge", "value": 5712.5}})
    assert read("estimator.tok_s_program", {}) == 5712.5
    monkeypatch.setattr(obs.metrics, "snapshot", lambda: {})
    assert read("estimator.tok_s_program", {}) is None


def test_the_references_adamw_against_hand_worked_numbers():
    """Two steps on a matrix and on a norm gain, worked by hand: the first
    Adam direction is g / (|g| + eps) whatever b1 and b2; the decay goes to
    the matrix alone; the second step's moments carry their corrections."""
    from benchmark.reference import ouro as ref

    w = np.full((2, 2), 0.5, np.float32)
    gain = np.ones(2, np.float32)
    g_w = np.array([[1e-3, -2e-3], [4e-3, -8e-3]], np.float32)
    g_n = np.array([3e-3, -3e-3], np.float32)
    lr, b1, b2, wd = 0.01, 0.9, 0.95, 0.1
    state = ref.adamw_init([w, gain])
    (w1, n1), state = ref.adamw_step([w, gain], [g_w, g_n], state, lr, b1, b2, wd)
    np.testing.assert_allclose(w1, 0.5 - lr * (np.sign(g_w) + wd * 0.5), rtol=1e-5)
    np.testing.assert_allclose(n1, 1.0 - lr * np.sign(g_n), rtol=1e-5)
    (w2, n2), state = ref.adamw_step([w1, n1], [g_w * 3, g_n], state, lr, b1, b2, wd)
    m_hat = (b1 * (1 - b1) * g_w + (1 - b1) * 3 * g_w) / (1 - b1 ** 2)
    v_hat = (b2 * (1 - b2) * g_w ** 2 + (1 - b2) * 9 * g_w ** 2) / (1 - b2 ** 2)
    np.testing.assert_allclose(
        w2, w1 - lr * (m_hat / (np.sqrt(v_hat) + 1e-8) + wd * w1), rtol=1e-5)
    np.testing.assert_allclose(n2, n1 - lr * np.sign(g_n), rtol=1e-5)
    assert state["count"] == 2 and w2.dtype == np.float32


def test_the_references_copy_imports_nothing_from_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference", "ouro.py")) as f:
        text = f.read()
    assert "import raydp_tpu" not in text and "from raydp_tpu" not in text
    assert 'default_matmul_precision("highest")' in text


def test_a_replayed_epoch_holds_the_right_update_and_refuses_a_wrong_one():
    """``Reference.epoch`` pushes whatever ``loss_and_grads`` gives through
    the reference's AdamW, and ``step_gaps`` measures an epoch's outcome
    against that replay: the same update reads 0; a rate 10 % off, a step
    left out and a loss off by a step's share read over the mix's
    ``step_own`` limits."""
    import jax

    lm = cells.load_module(cells.resolve(ROOT, CELL).driver_path, "lmfit")
    from benchmark.reference import ouro as ref

    rng = np.random.default_rng(3)
    theta0 = [rng.normal(0, 0.02, (16, 8)).astype(np.float32),
              np.ones((8,), np.float32)]
    grads = [[rng.normal(0, 1e-2, a.shape).astype(np.float32) for a in theta0]
             for _ in range(2)]
    treedef = jax.tree.structure(list(theta0))
    hyper = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "weight_decay": 0.1}
    calls = iter(range(2))

    def fixed(params, rows):
        i = next(calls)
        return {"loss": 10.0 - i, "grads": grads[i]}

    theta, losses = lm.Reference({}, 0).epoch(
        theta0, treedef, [None, None], hyper, fixed)
    assert losses == [10.0, 9.0]
    a = {"theta0": theta0, "theta_own": theta, "own_epoch_loss": 9.5,
         "groups": [[0], [1]]}
    limits = cells.resolve(ROOT, CELL).traffic["arith_tolerance"]["step_own"]

    def outcome(steps=2, **changed):
        leaves, state = theta0, ref.adamw_init(theta0)
        h = {**hyper, **changed}
        for g in grads[:steps]:
            leaves, state = ref.adamw_step(
                leaves, g, state, h["learning_rate"], h["b1"], h["b2"],
                h["weight_decay"])
        return lm.step_gaps(leaves, 9.5, a, "own")[0]

    same = outcome()
    assert same["change_rel"] < 1e-6 and same["change_norm"] < 1e-6
    assert same["loss_abs"] == 0.0
    assert outcome(learning_rate=3.3e-4)["change_norm"] > limits["change_norm"]
    assert outcome(steps=1)["change_rel"] > limits["change_rel"]
    assert lm.step_gaps(theta, 9.5 + 2 * limits["loss_abs"], a, "own")[0][
        "loss_abs"] > limits["loss_abs"]
