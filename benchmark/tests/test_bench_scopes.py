"""``harness/scopes.py`` (PR 38): the join of a trace's operations with the
program's map of scopes, on a hand-made ``ops`` dict and map; the readers that
read it, with a map and without one (a parent commit from before the map);
and every per-layer entry this PR adds resolved to its reader file."""

import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cells, layers, scopes, xplane  # noqa: E402

LAYOUT = "{1,0:T(8,128)(2,1)S(1)}"


def line(name, result, operation="fusion", rest=""):
    return (f"%{name} = {result} {operation}(bf16[8,16]{LAYOUT} %p.1), "
            f"kind=kLoop{rest}")


# the step program and the evaluation's: both have a %fusion.12 of their own
STEP = {
    "fusion.1": {"result": "bf16[8,16]", "scopes": ["loss_and_grad", "m.experts", "m.experts.gmm"]},
    "gmm.4": {"result": "bf16[8,16]", "scopes": ["loss_and_grad", "m.experts", "m.experts.gmm"]},
    "fusion.2": {"result": "(f32[8], /*index=1*/f32[8])", "scopes": ["loss_and_grad", "m.experts"], "mixed": True},
    "fusion.3": {"result": "f32[16,8]", "scopes": ["optimizer_update"], "mixed": True},
    "fusion.4": {"result": "f32[8,16]", "scopes": ["loss_and_grad", "m.loss"]},
    "copy.9": {"result": "f32[8,16]", "scopes": []},
    "fusion.12": {"result": "bf16[8,16]", "scopes": ["loss_and_grad", "m.mlp"]},
    "fusion.13": {"result": "bf16[8,16]", "scopes": ["loss_and_grad", "m.mlp"]},
    "cond.7": {"result": "(bf16[8,16])", "scopes": ["loss_and_grad", "m.experts"]},
}
EVAL = {
    "fusion.12": {"result": "f32[4]", "scopes": ["m.loss"]},  # told apart by its result
    "fusion.13": {"result": "bf16[8,16]", "scopes": ["m.loss"]},  # not told apart
    "fusion.4": {"result": "f32[8,16]", "scopes": ["loss_and_grad", "m.loss"]},  # the same scopes: no collision
}
PROGRAMS = {"3#1": STEP, "eval_scan#2": EVAL}

MOSAIC = ', custom_call_target="tpu_custom_call"'
OPS = {
    line("fusion.1", f"bf16[8,16]{LAYOUT}"): (9, 0.009000000001),
    line("gmm.4", f"bf16[8,16]{LAYOUT}", "custom-call", MOSAIC): (18, 0.0306),
    line("fusion.2", f"(f32[8]{{0:T(128)}}, /*index=1*/f32[8]{{0:T(128)}})"): (9, 0.0045),
    line("fusion.3", f"f32[16,8]{LAYOUT}"): (9, 0.018),
    line("fusion.4", f"f32[8,16]{LAYOUT}"): (12, 0.012),
    line("copy.9", f"f32[8,16]{LAYOUT}", "copy"): (9, 0.0009),
    line("fusion.12", f"bf16[8,16]{LAYOUT}"): (9, 0.0027),
    line("fusion.12", "f32[4]{0:T(128)}"): (3, 0.0003),
    line("fusion.13", f"bf16[8,16]{LAYOUT}"): (12, 0.0024),
    line("broadcast.77", "f32[]"): (3, 0.000001),  # a program nobody noted
    # containers: the kernels above run inside them
    line("cond.7", f"(bf16[8,16]{LAYOUT})", "conditional"): (9, 0.0400),
    line("conditional.3", f"(bf16[8,16]{LAYOUT})", "conditional"): (9, 0.0400),
    line("while.5", f"(s32[], bf16[8,16]{LAYOUT})", "while"): (3, 0.0900),
    line("scan_body.5", f"(s32[], bf16[8,16]{LAYOUT})", "call"): (3, 0.0100),
}
COUNTED = {k: v for k, v in OPS.items()
           if not k.startswith(("%cond", "%while", "%scan_body"))}


def ps(seconds):
    return round(seconds * 1e12)


def test_the_partition_sums_exactly_and_memberships_nest():
    made = scopes.join(OPS, PROGRAMS)
    total = sum(ps(s) for _, s in COUNTED.values())
    assert made.total_ps == total
    assert sum(row.ps for row in made.parts.values()) == total
    assert sum(op[2] for op in made.operations) == total
    assert {p: r.ps for p, r in made.parts.items()} == {
        "m.experts.gmm": ps(0.009000000001) + ps(0.0306),
        "m.experts": ps(0.0045), "optimizer_update": ps(0.018),
        "m.loss": ps(0.012) + ps(0.0003), "m.mlp": ps(0.0027),
        scopes.UNATTRIBUTED: ps(0.0009) + ps(0.000001),
        scopes.AMBIGUOUS: ps(0.0024)}
    assert made.members["m.experts"] == (
        made.parts["m.experts"].ps + made.parts["m.experts.gmm"].ps)
    assert made.members["m.experts.gmm"] == made.parts["m.experts.gmm"].ps
    assert "m.experts.route" not in made.members
    # the two halves of a step and what lies outside both
    halves = ("loss_and_grad", "optimizer_update")
    assert (made.members["loss_and_grad"] + made.members["optimizer_update"]
            + made.outside(*halves)) == total
    assert made.outside(*halves) == (
        ps(0.0009) + ps(0.000001) + ps(0.0024) + ps(0.0003))
    # calls, the mixed time and what no live program knows
    assert made.parts["m.experts.gmm"].calls == 27
    assert made.parts["m.experts"].mixed_ps == ps(0.0045)
    assert made.parts["m.experts.gmm"].mixed_ps == 0
    assert made.unknown_ps == ps(0.000001)
    assert made.blind_share() == pytest.approx(
        (0.0009 + 0.000001 + 0.0024) / (total / 1e12))


@pytest.mark.parametrize("name, is_one", [
    ("cond.7", True), ("conditional.3", True), ("while.5", True),
    ("scan_body.5", True),  # a call by its operation, whatever its name
    ("fusion.1", False), ("gmm.4", False), ("copy.9", False)])
def test_a_container_is_left_out(name, is_one):
    (found,) = [k for k in OPS if k.startswith(f"%{name} ")]
    assert scopes.is_container(found) is is_one
    made = scopes.join({found: OPS[found]}, PROGRAMS)
    assert (made.total_ps == 0) is is_one
    # xplane's own list does not know a forward conditional's name
    if name == "cond.7":
        assert not found.startswith(xplane.CONTAINERS)


def test_a_name_collision_lands_in_ambiguous_unless_the_result_tells():
    made = scopes.join(OPS, PROGRAMS)
    by_line = {op[0]: op for op in made.operations}
    assert by_line[line("fusion.13", f"bf16[8,16]{LAYOUT}")][3] == scopes.AMBIGUOUS
    assert by_line[line("fusion.12", f"bf16[8,16]{LAYOUT}")][3] == "m.mlp"
    assert by_line[line("fusion.12", "f32[4]{0:T(128)}")][3] == "m.loss"
    assert by_line[line("fusion.4", f"f32[8,16]{LAYOUT}")][3] == "m.loss"
    # one program alone: nothing to collide with
    alone = scopes.join(OPS, {"3#1": STEP})
    assert scopes.AMBIGUOUS not in alone.parts


def test_programs_that_agree_on_the_innermost_scope_share_an_operation():
    """A step's and an evaluation's forward pass: one region, in and out of
    ``loss_and_grad``. A trace pools their events where the lines read the
    same, so the time goes to the scopes both programs give."""
    gmm = ["m.experts", "m.experts.gmm"]
    programs = {
        "3#1": {"gmm.2": {"result": "bf16[8,16]", "scopes": ["loss_and_grad"] + gmm},
                "copy.5": {"result": "bf16[8,16]", "scopes": ["loss_and_grad", "m.mlp"]}},
        "eval_scan#2": {"gmm.2": {"result": "bf16[8,16]", "scopes": gmm, "mixed": True},
                        "copy.5": {"result": "bf16[8,16]", "scopes": []}}}
    ops = {line("gmm.2", f"bf16[8,16]{LAYOUT}", "custom-call", MOSAIC): (12, 0.012),
           line("copy.5", f"bf16[8,16]{LAYOUT}", "copy"): (12, 0.001)}
    made = scopes.join(ops, programs)
    assert made.parts["m.experts.gmm"].ps == ps(0.012)
    assert made.parts["m.experts.gmm"].mixed_ps == ps(0.012)
    assert made.members == {"m.experts": ps(0.012), "m.experts.gmm": ps(0.012)}
    # one of the two says it belongs nowhere: no agreement
    assert made.parts[scopes.AMBIGUOUS].ps == ps(0.001)
    assert made.outside("loss_and_grad") == made.total_ps
    assert "Mosaic calls %gmm: 12 calls 12.000 ms under m.experts.gmm" in (
        scopes.render(made, programs))


def test_the_table_names_kernels_halves_and_the_blind_spot():
    text = scopes.render(scopes.join(OPS, PROGRAMS), PROGRAMS)
    assert "Mosaic calls %gmm: 18 calls 30.600 ms under m.experts.gmm" in text
    assert "loss_and_grad" in text and "outside both" in text
    assert "unattributed: 0.900 ms in 9 calls of copy.9" in text
    assert "ambiguous: 2.400 ms in 12 calls of fusion.13" in text
    assert "3#1: 9, eval_scan#2: 3" in text


NEW = {
    "model.experts_scope_ms": ["lfm2-8b-a1b.pretrain-8k-routed"],
    "model.loss_scope_ms": [
        "ouro-2.6b.pretrain-4k", "granite-4.0-h-micro.pretrain-8k",
        "lfm2-8b-a1b.pretrain-8k-routed"],
    "model.ssd_scope_ms": ["granite-4.0-h-micro.pretrain-8k"],
    "estimator.optimizer_scope_ms": "all",
    "device.scope_unattributed_share": "all",
    "model.moe_likely_bound_share": ["lfm2-8b-a1b.pretrain-8k-routed"],
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_a_new_entry_has_its_reader_file_in_its_cells(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    every = [w["name"] for w in bench["workloads"]]
    assert entry["workloads"] == (every if NEW[name] == "all" else NEW[name])
    assert entry["moves"] == "fit_samples_per_s"
    assert entry["layer"] in {m["layer"] for m in bench["per_layer"]
                              if m["name"] not in NEW}
    for workload in entry["workloads"]:
        cell = cells.resolve(ROOT, workload)
        assert cell.layer_files[name].endswith(
            os.path.join("layer_metrics", name + ".py"))


@pytest.fixture()
def sources(monkeypatch):
    """A traced LM run's sources over OPS, the program giving PROGRAMS."""
    from raydp_tpu.obs import profiler

    names = {"model.loss_scope_ms": "m.loss", "model.ssd_scope_ms": "m.ssd",
             "model.experts_scope_ms": "m.experts"}
    programs = json.loads(json.dumps(PROGRAMS)
                          .replace("m.loss", "hybridlm.loss")
                          .replace("m.experts", "hybridlm.experts"))
    monkeypatch.setattr(profiler, "device_scopes", lambda: programs,
                        raising=False)
    monkeypatch.setattr(scopes, "_made", [])
    monkeypatch.setattr(scopes, "_printed", False)
    trace = types.SimpleNamespace(ops=OPS)
    return {"trace": trace, "values": {"steps_in_trace": 9}}, names


def read(name, sources):
    path = os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py")
    return layers.read_metric(path, sources)


def test_the_readers_read_the_join(sources, capsys):
    sources, _ = sources
    assert read("model.experts_scope_ms", sources) == pytest.approx(
        1e3 * (0.009000000001 + 0.0306 + 0.0045) / 9)
    assert read("model.loss_scope_ms", sources) == pytest.approx(
        1e3 * (0.012 + 0.0003) / 9)
    assert read("estimator.optimizer_scope_ms", sources) == pytest.approx(
        1e3 * 0.018 / 9)
    assert read("device.scope_unattributed_share", sources) == pytest.approx(
        100 * (0.0009 + 0.000001 + 0.0024) / 0.080401000001)
    assert read("model.ssd_scope_ms", sources) is None  # nothing lies there
    # one join and one printed table for all of them
    assert len(scopes._made) == 1
    assert capsys.readouterr().err.count("device time by scope") == 1
    # the DLRM cells give no count of their own: the commonest call count
    sources["values"] = {}
    assert scopes.steps(sources) == 9
    assert read("estimator.optimizer_scope_ms", sources) == pytest.approx(
        1e3 * 0.018 / 9)
    assert read("model.loss_scope_ms", sources) is None


@pytest.mark.parametrize("name", sorted(set(NEW) - {"model.moe_likely_bound_share"}))
def test_a_program_without_the_map_gives_nothing_and_does_not_raise(
        name, sources, monkeypatch):
    from raydp_tpu.obs import profiler

    sources, _ = sources
    monkeypatch.delattr(profiler, "device_scopes")  # the parent commit
    assert read(name, sources) is None
    monkeypatch.setattr(profiler, "device_scopes", lambda: {}, raising=False)
    monkeypatch.setattr(scopes, "_made", [])
    assert read(name, sources) is None  # every program already collected
    assert read(name, {"values": {"steps_in_trace": 9}}) is None  # no trace


def test_the_likely_bound_share_is_the_programs_gauge(monkeypatch):
    from raydp_tpu import obs

    snap = {}
    monkeypatch.setattr(obs.metrics, "snapshot", lambda: snap)
    assert read("model.moe_likely_bound_share", {}) is None
    snap["model.experts.likely_bound_share"] = {"type": "gauge", "value": 0.875}
    assert read("model.moe_likely_bound_share", {}) == 87.5
