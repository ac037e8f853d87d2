"""chip_smoke.py's contract off the chip: the CPU rehearsal runs all four
phases green at tiny sizes and prints no device metric; without the rehearsal
argument, or without the repo around it, it exits non-zero with no result."""

import json
import os
import re
import shutil
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO_ROOT, "chip_smoke.py")


def _run(args, cwd=REPO_ROOT, script=SMOKE):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)  # one CPU device: the one-chip control flow
    return subprocess.run(
        [sys.executable, script, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600,
    )


def _result_lines(stdout):
    found = []
    for line in stdout.splitlines():
        if line.startswith("{"):
            found.append(json.loads(line))
    return found


def test_rehearsal_runs_all_phases_and_prints_no_device_metric():
    done = _run(["--rehearse-on-cpu"])
    assert done.returncode == 0, done.stdout[-4000:] + done.stderr[-2000:]
    lines = [ln for ln in done.stdout.splitlines() if ln.strip()]
    summary = json.loads(lines[-1])
    assert summary["rehearsal"] is True and summary["ok"] is True
    assert summary["phases"] == ["kernels", "fit", "lm", "serve"]
    assert summary["device"]["platform"] == "cpu"
    assert list(summary)[-1] == "claim" and summary["claim"] is None
    for line in lines[:-1]:
        # every line says it is a rehearsal; none carries a time or a rate
        assert "REHEARSAL" in line, line
        assert not re.search(r"\d(\.\d+)? ?(s|ms|us)\b|/s\b|MFU|tok/s", line), line
    for phase in summary["phases"]:
        assert f"[{phase}] ok" in done.stdout


def test_without_a_tpu_it_exits_nonzero_and_names_the_platform():
    done = _run([])
    assert done.returncode != 0
    assert "platform is 'cpu'" in done.stdout
    assert "no result" in done.stdout
    assert _result_lines(done.stdout) == []


def test_alone_in_a_directory_it_fails_without_a_result(tmp_path):
    lone = shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    done = _run([], cwd=str(tmp_path), script=str(lone))
    assert done.returncode != 0
    assert _result_lines(done.stdout) == []


def test_result_line_has_exactly_the_contract_keys():
    """The chip run's last stdout line: {"ok", "device": {"platform", "kind",
    "count"}} and nothing else — the summary (ending in "claim") is the line
    before it."""
    sys.path.insert(0, REPO_ROOT)
    try:
        import chip_smoke
    finally:
        sys.path.remove(REPO_ROOT)
    line = chip_smoke.result_line(
        True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
               "extra": "dropped"})
    assert "\n" not in line
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }
    assert json.loads(chip_smoke.result_line(False, {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 4}))["ok"] is False
