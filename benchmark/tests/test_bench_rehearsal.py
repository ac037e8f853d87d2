"""``--rehearse-on-cpu`` of every cell, both trace modes: the whole control
flow at tiny sizes, no time printed; and the failures the contract asks for."""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def run(args, cwd=ROOT, env=None, timeout=600):
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=timeout)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_runs_the_cell(cell, trace):
    proc = run(["--workload", cell, "--seed", str(2**31 + 5), "--seconds", "2",
                "--trace", str(trace), "--rehearse-on-cpu"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-2000:]
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["rehearsal"] is True and last["correct"] is True
    assert last["metrics"] == {} and last["device"]["platform"] == "cpu"
    assert last["attempted"] > 0 and last["failed"] == 0
    told = [ln for ln in proc.stdout.splitlines() if ln.startswith("[")]
    assert told and all(ln.startswith("[REHEARSAL") for ln in told)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0"], env=env)
    assert proc.returncode != 0
    assert '"metrics"' not in proc.stdout and '"correct"' not in proc.stdout


def test_without_the_program_the_run_fails_and_prints_no_result(tmp_path):
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    proc = run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                "--trace", "0"], cwd=str(tmp_path))
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_the_parent_never_imports_jax():
    code = ("import sys; sys.argv=['run.py','--workload','x']; "
            "import runpy\n"
            "try:\n    runpy.run_path('benchmark/run.py', run_name='__main__')\n"
            "except SystemExit: pass\n"
            "assert 'jax' not in sys.modules, 'parent imported jax'")
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
