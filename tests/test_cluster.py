"""Cluster runtime tests.

Mirrors the reference's cluster test areas (test_spark_cluster.py): lifecycle,
named actors, restarts (parity: setMaxRestarts, RayExecutorUtils.java:63),
intentional-exit-no-restart (ApplicationInfo.scala:119-124), placement group
strategies (test_placement_group, test_spark_cluster.py:127-164), node
kill/re-add elasticity (test_reconstruction, test_spark_cluster.py:166-196).
"""

import os
import time

import pytest

from raydp_tpu import cluster
from raydp_tpu.cluster import ActorDiedError, ActorState, ClusterError


class Counter:
    def __init__(self, start=0):
        self.value = start

    def incr(self, by=1):
        self.value += by
        return self.value

    def get(self):
        return self.value

    def pid(self):
        return os.getpid()

    def node_ip(self):
        return cluster.current_context().node_ip

    def boom(self):
        raise ValueError("boom from actor")

    def die(self):
        os._exit(1)

    def leave(self):
        cluster.exit_actor()


class Sleeper:
    def nap(self, seconds):
        time.sleep(seconds)
        return "rested"

    def quick(self):
        return "quick"


@pytest.fixture(scope="module")
def runtime():
    cluster.init(num_cpus=8, memory=2 << 30)
    yield
    cluster.shutdown()


def test_spawn_call_roundtrip(runtime):
    c = cluster.spawn(Counter, 10, name="counter1")
    assert c.incr.remote(5).result() == 15
    assert c.get() == 15  # sync sugar
    c.kill()


def test_actor_exception_propagates(runtime):
    c = cluster.spawn(Counter)
    with pytest.raises(ValueError, match="boom from actor"):
        c.boom.remote().result()
    # actor still alive after a user exception
    assert c.incr.remote().result() == 1
    c.kill()


def test_named_actor_lookup_and_pickled_handle(runtime):
    c = cluster.spawn(Counter, name="lookup-me")
    h = cluster.get_actor("lookup-me")
    assert h.incr.remote(7).result() == 7

    # a handle passed into another actor must work there
    class Caller:
        def __init__(self, handle):
            self.handle = handle

        def bump(self):
            return self.handle.incr.remote(1).result()

    caller = cluster.spawn(Caller, h)
    assert caller.bump.remote().result() == 8
    caller.kill()
    c.kill()


def test_crash_restarts_with_same_identity(runtime):
    c = cluster.spawn(Counter, name="phoenix", max_restarts=2)
    pid1 = c.pid.remote().result()
    try:
        c.die.remote().result()
    except (ConnectionError, OSError, ClusterError):
        pass
    # restarted: same name, fresh state, new pid
    deadline = time.monotonic() + 30
    while True:
        try:
            pid2 = c.pid.remote().result()
            break
        except (ConnectionError, OSError):
            assert time.monotonic() < deadline
            time.sleep(0.1)
    assert pid2 != pid1
    assert c.get.remote().result() == 0  # state reset on restart
    record = cluster.get_actor("phoenix")._record()
    assert record.restarts_used == 1
    c.kill()


def test_intentional_exit_is_not_restarted(runtime):
    c = cluster.spawn(Counter, name="quitter", max_restarts=5)
    try:
        c.leave.remote().result()
    except (ConnectionError, OSError, ClusterError):
        pass
    deadline = time.monotonic() + 10
    while c.state() != ActorState.DEAD:
        assert time.monotonic() < deadline
        time.sleep(0.05)
    with pytest.raises(ActorDiedError):
        c.get.remote().result()


def test_crash_past_max_restarts_dies(runtime):
    c = cluster.spawn(Counter, max_restarts=0)
    try:
        c.die.remote().result()
    except (ConnectionError, OSError, ClusterError):
        pass
    deadline = time.monotonic() + 10
    while c.state() != ActorState.DEAD:
        assert time.monotonic() < deadline
        time.sleep(0.05)
    with pytest.raises(ActorDiedError):
        c.incr.remote().result()


def test_max_concurrency_allows_parallel_calls(runtime):
    s = cluster.spawn(Sleeper, max_concurrency=2)
    slow = s.nap.remote(1.5)
    t0 = time.monotonic()
    assert s.quick.remote().result(timeout=5) == "quick"
    quick_elapsed = time.monotonic() - t0
    assert quick_elapsed < 1.2, f"quick call waited behind nap: {quick_elapsed:.2f}s"
    assert slow.result(timeout=10) == "rested"
    s.kill()


def test_resource_accounting_and_release(runtime):
    before = sum(a.get("CPU", 0) for a in cluster.available_resources().values())
    c = cluster.spawn(Counter, num_cpus=2)
    during = sum(a.get("CPU", 0) for a in cluster.available_resources().values())
    assert during == pytest.approx(before - 2)
    c.kill()
    deadline = time.monotonic() + 10
    while True:
        after = sum(a.get("CPU", 0) for a in cluster.available_resources().values())
        if after == pytest.approx(before):
            break
        assert time.monotonic() < deadline
        time.sleep(0.05)


def test_fractional_cpu(runtime):
    # parity: fractional spark.ray.actor.resource.cpu (conftest.py:76-113)
    a = cluster.spawn(Counter, num_cpus=0.5)
    b = cluster.spawn(Counter, num_cpus=0.5)
    assert a.incr.remote().result() == 1
    assert b.incr.remote().result() == 1
    a.kill()
    b.kill()


def test_oversubscription_rejected(runtime):
    with pytest.raises(ClusterError, match="no node can host"):
        cluster.spawn(Counter, num_cpus=10_000)


def test_placement_group_strategies(runtime):
    # STRICT_SPREAD with more bundles than alive nodes must fail (node count
    # is dynamic: other test modules may have registered agent nodes)
    n_nodes = len([n for n in cluster.nodes() if n.alive])
    with pytest.raises(ClusterError, match="STRICT_SPREAD"):
        cluster.create_placement_group(
            [{"CPU": 1}] * (n_nodes + 1), "STRICT_SPREAD"
        )
    # ...but PACK/STRICT_PACK fit, actors land in bundles, removal frees resources
    pg = cluster.create_placement_group([{"CPU": 1}, {"CPU": 1}], "STRICT_PACK")
    table = cluster.placement_group_table()
    assert table[pg.id]["strategy"] == "STRICT_PACK"
    nodes = {b["node_id"] for b in table[pg.id]["bundles"]}
    assert len(nodes) == 1
    a = cluster.spawn(Counter, num_cpus=1, placement_group=pg.id, bundle_index=0)
    assert a.incr.remote().result() == 1
    with pytest.raises(ClusterError, match="bundle"):
        cluster.spawn(Counter, num_cpus=1, placement_group=pg.id, bundle_index=0)
    a.kill()
    cluster.remove_placement_group(pg)
    assert pg.id not in cluster.placement_group_table()


def test_multinode_spread_and_node_kill(runtime):
    n1 = cluster.add_node({"CPU": 2})
    n2 = cluster.add_node({"CPU": 2})
    try:
        pg = cluster.create_placement_group([{"CPU": 1}, {"CPU": 1}], "STRICT_SPREAD")
        table = cluster.placement_group_table()
        bundle_nodes = {b["node_id"] for b in table[pg.id]["bundles"]}
        assert len(bundle_nodes) == 2
        cluster.remove_placement_group(pg)

        # an actor bound to a custom resource only n3 has; kill n3 → actor is
        # pending; re-add capacity → actor respawns there (elasticity, parity:
        # test_reconstruction's kill-node/re-add-node dance)
        n3 = cluster.add_node({"CPU": 1, "special": 1})
        ip3 = next(n.node_ip for n in cluster.nodes() if n.node_id == n3)
        a = cluster.spawn(Counter, name="migrant", max_restarts=3,
                          resources={"special": 1})
        assert a.node_ip.remote().result() == ip3
        cluster.remove_node(n3)
        time.sleep(0.5)  # actor should now be RESTARTING with nowhere to go
        assert a.state() in (ActorState.RESTARTING, ActorState.PENDING)
        n4 = cluster.add_node({"CPU": 1, "special": 1})
        ip4 = next(n.node_ip for n in cluster.nodes() if n.node_id == n4)
        deadline = time.monotonic() + 30
        while True:
            try:
                if a.node_ip.remote().result() == ip4:
                    break
            except (ConnectionError, OSError, ClusterError):
                pass
            assert time.monotonic() < deadline, "actor never respawned on new node"
            time.sleep(0.1)
        a.kill()
        cluster.remove_node(n4)
    finally:
        cluster.remove_node(n1)


def test_global_zygote_key_and_guards(tmp_path):
    """The machine-global zygote's safety rails: the source key changes when
    any module's mtime changes (stale templates can never serve new code),
    and marker liveness is identity-checked by (pid, starttime) so a REUSED
    pid — even one whose fork-inherited cmdline still looks like a zygote —
    reads as dead instead of latching adoption onto an impostor."""
    from raydp_tpu.cluster.common import (
        _marker_pid_alive,
        _pid_alive_not_zombie,
        _proc_starttime,
        _write_zygote_marker,
        _zygote_source_key,
    )

    key1 = _zygote_source_key()
    assert key1 == _zygote_source_key()  # stable while nothing changes

    import raydp_tpu

    probe_file = os.path.join(
        os.path.dirname(os.path.abspath(raydp_tpu.__file__)), "utils.py"
    )
    st = os.stat(probe_file)
    try:
        os.utime(probe_file, ns=(st.st_atime_ns, st.st_mtime_ns + 1))
        assert _zygote_source_key() != key1
    finally:
        os.utime(probe_file, ns=(st.st_atime_ns, st.st_mtime_ns))
    assert _zygote_source_key() == key1

    assert _pid_alive_not_zombie(os.getpid())
    marker = str(tmp_path / "zygote.pid")
    _write_zygote_marker(marker, os.getpid())
    assert _marker_pid_alive(marker) == os.getpid()  # same incarnation
    # simulate pid reuse: same pid, different recorded starttime
    with open(marker + ".start", "w") as f:
        f.write(str(_proc_starttime(os.getpid()) - 1))
    assert _marker_pid_alive(marker) is None
    # dead pid
    _write_zygote_marker(marker, 2**22 + 12345)  # almost surely unused
    assert _marker_pid_alive(marker) is None


def test_jax_warm_zygote_keys_on_the_jax_environment(monkeypatch):
    """jax.config reads its environment once, at import: a child forked from
    a template that imported jax would serve under the TEMPLATE's
    JAX_COMPILATION_CACHE_DIR / JAX_PLATFORMS whatever its own environment
    says. So a jax-warm template is only shared between drivers whose JAX
    environment agrees; a plain one (children import jax themselves, after
    adopting their environment) is shared as before."""
    from raydp_tpu.cluster.common import _zygote_source_key
    from raydp_tpu.cluster.zygote import WARM_JAX_ENV

    monkeypatch.delenv(WARM_JAX_ENV, raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/a")
    plain = _zygote_source_key()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/b")
    assert _zygote_source_key() == plain

    monkeypatch.setenv(WARM_JAX_ENV, "1")
    warm_b = _zygote_source_key()
    assert warm_b != plain
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/a")
    assert _zygote_source_key() != warm_b
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/b")
    assert _zygote_source_key() == warm_b


def test_zygote_adoption_stamp_blocks_idle_retirement(tmp_path):
    """ADVICE r5 regression: the idle clock is bumped UNDER the adoption
    flock (lock-protected adoption stamp) and the retirement path re-checks
    it after acquiring the same lock — a template exactly at its idle TTL
    can no longer retire right after a session adopted it (the old
    post-unlock socket poke left exactly that window)."""
    from raydp_tpu.cluster.zygote import (
        GLOBAL_IDLE_TTL_S,
        adoption_recent,
        adoption_stamp_path,
        touch_adoption_stamp,
    )

    gdir = str(tmp_path)
    # no adoption ever: nothing vetoes retirement
    assert not adoption_recent(gdir, GLOBAL_IDLE_TTL_S)
    # a fresh stamp (what _adopt_global_zygote writes while HOLDING the
    # flock) vetoes retirement even though the fork-based idle clock is
    # stale — the exact interleaving of the race
    touch_adoption_stamp(gdir)
    assert adoption_recent(gdir, GLOBAL_IDLE_TTL_S)
    # an adoption older than the TTL no longer vetoes: the adopting session
    # got a full TTL of service and the template may retire
    stamp = adoption_stamp_path(gdir)
    old = time.time() - (GLOBAL_IDLE_TTL_S + 60)
    os.utime(stamp, (old, old))
    assert not adoption_recent(gdir, GLOBAL_IDLE_TTL_S)


def test_global_zygote_adoption_writes_stamp(tmp_path, monkeypatch):
    """_adopt_global_zygote leaves the lock-protected adoption stamp in the
    global template dir (the retirement veto reads it under the same lock)."""
    import signal
    import tempfile

    from raydp_tpu.cluster import common
    from raydp_tpu.cluster.zygote import adoption_recent, zygote_marker_path

    monkeypatch.setattr(tempfile, "gettempdir", lambda: str(tmp_path))
    run_dir = tmp_path / "session"
    run_dir.mkdir()
    root = tmp_path / f"raydp_tpu-zygote-{os.getuid()}"
    try:
        assert common._adopt_global_zygote(str(run_dir), dict(os.environ))
        gdirs = [d for d in root.iterdir() if (d / "zygote.pid").exists()]
        assert len(gdirs) == 1
        assert adoption_recent(str(gdirs[0]), 60.0)
    finally:
        # the global template ignores parent death by design — kill whatever
        # adoption spawned, even if an assertion above already failed
        for marker in root.glob("*/zygote.pid") if root.exists() else ():
            try:
                os.kill(int(marker.read_text().strip()), signal.SIGKILL)
            except (OSError, ValueError):
                pass


@pytest.mark.skipif(
    bool(os.environ.get("RAYDP_TPU_TEST_ATTACH_TCP")),
    reason="introspects the head host's session dir (zygote marker files); "
    "a tcp-attached driver has its own client dir",
)
def test_zygote_restarts_after_death(runtime):
    """The head's monitor restarts a dead zygote (reaping the zombie — a
    bare pid probe would see it alive forever) and spawns stay fork-fast."""
    import signal
    import socket
    import time

    from raydp_tpu.cluster.zygote import zygote_marker_path, zygote_sock_path

    sd = cluster.session_dir()
    with open(zygote_marker_path(sd)) as f:
        pid1 = int(f.read())
    os.kill(pid1, signal.SIGKILL)
    deadline = time.monotonic() + 10.0
    pid2 = pid1
    while pid2 == pid1 and time.monotonic() < deadline:
        time.sleep(0.3)
        with open(zygote_marker_path(sd)) as f:
            pid2 = int(f.read())
    assert pid2 != pid1, "watchdog did not restart the zygote"

    # wait out the new zygote's import warm-up (socket binds after it) so
    # the timed spawn below measures only the fork path, not warm-up
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        try:
            s.connect(zygote_sock_path(sd))
            s.close()
            break
        except OSError:
            s.close()
            time.sleep(0.1)

    class Pinger:
        def ping(self):
            return 42

    t0 = time.monotonic()
    h = cluster.spawn(Pinger, name="zygote-restart-probe", light=True)
    spawn_s = time.monotonic() - t0
    try:
        assert h.ping.remote().result() == 42
        assert spawn_s < 1.0, f"spawn took {spawn_s:.2f}s — cold fallback?"
    finally:
        h.kill()


def test_agent_spawn_fence_ordering(tmp_path, monkeypatch):
    """Spawn RPCs land on agent server threads, so a delayed STALE spawn
    (the fenced-out incarnation whose reply the head lost) can arrive after
    the newer respawn already runs on the agent. Ordering — not inequality —
    must decide who dies: the stale spawn is refused (its proc reaped), and
    the newer healthy worker is never killed or displaced."""
    import cloudpickle

    from raydp_tpu.cluster import agent as agent_mod
    from raydp_tpu.cluster.common import ActorSpec

    launched, killed = [], []

    class FakeProc:
        def __init__(self, incarnation):
            self.pid = 10_000 + len(launched)
            self.incarnation = incarnation

        def poll(self):
            return None  # alive until explicitly "killed" below

    def fake_launch(spec, incarnation, run_dir, env):
        proc = FakeProc(incarnation)
        launched.append(proc)
        return proc

    import raydp_tpu.cluster.common as common_mod

    monkeypatch.setattr(common_mod, "launch_worker", fake_launch)
    monkeypatch.setattr(agent_mod.os, "killpg", lambda pid, sig: killed.append(pid))

    agent = agent_mod.NodeAgent(
        "tcp://127.0.0.1:1", "127.0.0.1", {}, "test-ns", str(tmp_path)
    )
    blob = cloudpickle.dumps(Counter)
    spec = ActorSpec(
        actor_id="a1",
        name=None,
        cls_blob=blob,
        args_blob=cloudpickle.dumps(((), {})),
        resources={},
    )

    # incarnation 2 (the healthy respawn) lands first
    assert agent.handle_spawn_actor(spec, 2, "") is True
    healthy = agent.children["a1"].proc

    # the delayed stale incarnation-1 spawn must be refused pre-fork
    assert agent.handle_spawn_actor(spec, 1, "") is False
    assert agent.children["a1"].proc is healthy
    assert healthy.pid not in killed
    assert len(launched) == 1  # fenced BEFORE forking

    # a duplicate delivery of the current incarnation is a no-op too
    assert agent.handle_spawn_actor(spec, 2, "") is False
    assert agent.children["a1"].proc is healthy

    # a genuinely newer incarnation replaces (and kills) the old worker
    assert agent.handle_spawn_actor(spec, 3, "") is True
    assert agent.children["a1"].incarnation == 3
    assert healthy.pid in killed

    # the fence must survive the children-table entry: after the monitor
    # reports a death and deletes the entry, a delayed stale spawn must
    # STILL be refused, or it would resurrect a fenced-out incarnation as
    # a leaked live process nothing ever kills
    del agent.children["a1"]
    assert agent.handle_spawn_actor(spec, 2, "") is False
    assert "a1" not in agent.children
    assert agent.handle_spawn_actor(spec, 4, "") is True


@pytest.mark.skipif(
    bool(os.environ.get("RAYDP_TPU_TEST_ATTACH_TCP")),
    reason="globs the head host's session dir for exit markers; a "
    "tcp-attached driver has its own client dir",
)
def test_zygote_exit_marker_records_death(runtime):
    """The zygote reaps its forked children, so monitors hold only a pid; the
    ``<log_base>.exit`` marker is what lets ZygoteProc.poll see a death even
    after pid reuse (ADVICE r3: raw pid probes can report alive forever)."""
    import glob
    import signal

    class Mortal:
        def pid(self):
            return os.getpid()

    h = cluster.spawn(Mortal, name="exit-marker-probe", light=True)
    worker_pid = h.pid.remote().result()
    os.kill(worker_pid, signal.SIGKILL)
    sd = cluster.session_dir()
    # pin the glob to THIS worker's log_base: the session dir is shared
    # across the module, and another test's marker must not satisfy (or
    # confuse) this assertion
    pattern = os.path.join(sd, f"a-{h._actor_id}-*.exit")
    deadline = time.monotonic() + 10.0
    markers = []
    while time.monotonic() < deadline:
        markers = [p for p in glob.glob(pattern) if os.path.getsize(p) > 0]
        if markers:
            break
        time.sleep(0.1)
    assert markers, "zygote wrote no .exit marker for a SIGKILLed child"
    codes = {open(p).read().strip() for p in markers}
    assert str(-signal.SIGKILL) in codes  # waitstatus_to_exitcode convention
    h.kill(no_restart=True)
