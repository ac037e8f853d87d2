"""Test scaffolding.

Mirrors the reference's test strategy (SURVEY.md §4): real multi-process cluster
on one machine, no mocks. JAX runs on a virtual 8-device CPU mesh so every
sharding/collective path is exercised without TPU hardware; the driver's bench
and dryrun validate the same code on real chips.
"""

import os
import sys

# Must be set before jax (or anything importing jax) loads. Force CPU even if
# the environment points at a real TPU: tests exercise sharding on the
# virtual 8-device mesh; chip_smoke.py targets the real chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "3")
# runtime sanitizers (raydp_tpu/sanitize.py): ON for the whole suite —
# `donation` fails loudly on externally-owned host aliases reaching donated
# jits (the PR 2 streaming-NaN class), `lockdep` raises LockOrderError the
# moment any lock acquisition closes an order cycle (even when the run never
# actually deadlocks), and `leaks` makes cluster/worker teardown audit
# threads/fds/shm segments/spill files back to the startup baseline
# (sanitize.leaked_* gauges). Default off outside tests.
os.environ.setdefault("RAYDP_TPU_SANITIZE", "donation,lockdep,leaks")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow", action="store_true", default=False,
        help="also run tests marked slow (examples, TF/torch estimators)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long-running test (examples-as-tests, multi-process "
        "estimators); excluded by default — run with --runslow or RUN_SLOW=1 "
        "(the reference splits its CI the same way, raydp.yml markers)",
    )


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow") or os.environ.get(
        "RUN_SLOW", ""
    ).lower() in ("1", "true", "yes"):
        return
    skip = pytest.mark.skip(reason="slow: run with --runslow or RUN_SLOW=1")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devices = jax.devices()
    assert len(devices) >= 8, f"expected 8 virtual CPU devices, got {devices}"
    return devices


# ---------------------------------------------------------------------------
# two-mode matrix (reference conftest.py:45-52 runs every test locally AND
# through a ray:// client driver): with RAYDP_TPU_TEST_ATTACH_TCP=1, every
# cluster.init() in the suite starts a DEDICATED server cluster in a separate
# process (with exactly the resources the test asked for) and attaches this
# driver to it over tcp:// with the auth token — so the whole module runs
# through the client attach path (auth, shm namespaces, proxied puts,
# cross-namespace reads), and destructive tests (node kills, zygote kills)
# hit their own throwaway cluster namespace.
# ---------------------------------------------------------------------------

ATTACH_TCP_ENV = "RAYDP_TPU_TEST_ATTACH_TCP"

if os.environ.get(ATTACH_TCP_ENV):
    import atexit
    import json
    import subprocess

    import raydp_tpu.cluster
    import raydp_tpu.cluster.api as _capi

    _real_shutdown = _capi.shutdown
    _server_procs = []

    _SERVER_CODE = """
import json, sys, time
from raydp_tpu.cluster import api
kwargs = json.loads(sys.argv[1])
sd = api.init(**kwargs)
print(json.dumps({"tcp": api.head_tcp_addr(), "token": api.cluster_token()}),
      flush=True)
while True:
    time.sleep(3600)
"""

    def _attach_init(num_cpus=None, memory=None, resources=None, session_root=None):
        if _capi._session_dir is not None:
            return _capi._session_dir
        env = dict(os.environ)
        env.pop(ATTACH_TCP_ENV, None)
        # the server is the cluster OWNER: it must not itself attach
        for var in ("RAYDP_TPU_SESSION", "RAYDP_TPU_HEAD_ADDR",
                    "RAYDP_TPU_TOKEN", "RAYDP_TPU_SHM_NS"):
            env.pop(var, None)
        env["PYTHONPATH"] = os.pathsep.join(p for p in sys.path if p)
        kwargs = {"num_cpus": num_cpus, "memory": memory, "resources": resources}
        proc = subprocess.Popen(
            [sys.executable, "-c", _SERVER_CODE, json.dumps(kwargs)],
            stdout=subprocess.PIPE, text=True, env=env,
        )
        _server_procs.append(proc)
        line = proc.stdout.readline()
        info = json.loads(line)
        return _capi.connect_cluster(info["tcp"], token=info["token"])

    def _attach_shutdown(*args, **kwargs):
        _real_shutdown(*args, **kwargs)  # client mode: detaches only
        while _server_procs:
            proc = _server_procs.pop()
            proc.terminate()  # SIGTERM → the server's atexit tears down
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()

    _capi.init = _attach_init
    _capi.shutdown = _attach_shutdown
    raydp_tpu.cluster.init = _attach_init
    raydp_tpu.cluster.shutdown = _attach_shutdown
    atexit.register(_attach_shutdown)
