"""Per-host block service tests (store/block_service.py,
docs/fault_tolerance.md "Ownership tiers"): executor death loses zero
blocks.

- completed executor blocks are SERVICE-owned (the handoff rides the
  batched registration frame; the head records the effective owner and the
  writer's pushed metas carry it);
- executor SIGKILL: byte-identical reads with ZERO re-executed tasks;
- scale-in with service ownership loses no data and issues ZERO
  ``object_reown_all`` RPCs;
- ``store.block_service=false`` restores the PR 8 executor-owned behavior
  (the A/B parity arm: the same kill recovers via lineage);
- a DEAD service degrades to lineage recovery, and the dead-owner fast
  path still short-circuits stale cached locations with zero head RPCs;
- the block-fetch retry ladder backs off with jitter and degrades to a
  lost-block-shaped error at its deadline instead of surfacing a raw
  ConnectionRefusedError.
"""

import os
import time

import pytest

import raydp_tpu
from raydp_tpu import obs
from raydp_tpu.cluster import api as cluster
from raydp_tpu.cluster.common import ActorState, ClusterError, OwnerDiedError
from raydp_tpu.etl import functions as F
from raydp_tpu.etl import tasks as T
from raydp_tpu.exchange import dataframe_to_dataset, dataset_to_dataframe
from raydp_tpu.store import block_service as bs
from raydp_tpu.store import object_store as store
from tools import chaos


@pytest.fixture()
def session():
    s = raydp_tpu.init_etl(
        "test-blocksvc", num_executors=2, executor_cores=1,
        executor_memory="300M",
    )
    yield s
    raydp_tpu.stop_etl()


def _reexecuted() -> int:
    return int(obs.metrics.counter("lineage.reexecuted_tasks").value)


def _materialized(session, rows=20_000, parts=4):
    src = session.range(rows, num_partitions=parts).with_column(
        "k", F.col("id") % 7
    )
    return dataframe_to_dataset(src)


# ---------------------------------------------------------------------------
# the handoff: completed blocks are service-owned
# ---------------------------------------------------------------------------


def test_executor_blocks_are_service_owned(session):
    """Every block a query produces through the executors is owned by the
    per-host service, not the producing executor — and the head's
    owner-kind table maps this host's namespace to the service."""
    ds = _materialized(session)
    service_id = session.block_service._actor_id
    assert {store.owner_of(b) for b in ds.blocks} == {service_id}
    # the owner-kind table is (namespace, tenant)-keyed: the session's
    # service serves ITS tenant, and no tenant-less fallback exists for it
    assert bs.service_for_namespace("", tenant=session.tenant_ns) == service_id
    assert bs.service_for_namespace("") is None
    # the writer's pushed metas / caches carry the EFFECTIVE owner too:
    # a read-warmed cached location must name the service, not an executor
    assert T.read_table_block(ds.blocks[0]).num_rows > 0
    meta = store.cached_location(ds.blocks[0].object_id)
    assert meta is not None and meta["owner"] == service_id


def test_executor_sigkill_loses_zero_blocks(session):
    """The headline contract: executor SIGKILL (no restart — previously
    real loss) is invisible with the service owning blocks: reads stay
    byte-identical and lineage re-executes NOTHING."""
    ds = _materialized(session)
    df = dataset_to_dataframe(session, ds)
    clean = df.group_by("k").count().sort("k").collect()
    before = _reexecuted()
    chaos.kill_executor(session, index=0)
    time.sleep(0.3)
    assert df.group_by("k").count().sort("k").collect() == clean
    assert ds.to_arrow().num_rows == 20_000
    assert _reexecuted() - before == 0


def test_scale_in_with_service_zero_reown_rpcs(session):
    """kill_executors skips the object_reown_all sweep entirely when the
    service owns the blocks — and loses no data doing so."""
    ds = _materialized(session, rows=8_000)
    before = obs.metrics.counter("rpc.client.calls.object_reown_all").value
    session.kill_executors(1, min_keep=1)
    after = obs.metrics.counter("rpc.client.calls.object_reown_all").value
    assert after - before == 0
    assert ds.to_arrow().num_rows == 8_000
    assert dataset_to_dataframe(session, ds).count() == 8_000


def test_service_crash_restart_keeps_blocks_readable(session):
    """The service is stateless by design: a CRASH (restarts left) keeps
    the same actor identity, so ownership records stay valid and the
    segments were never touched — no recovery, no re-execution."""
    ds = _materialized(session, rows=6_000)
    before = _reexecuted()
    svc = session.block_service
    restarts = svc._record().restarts_used
    svc.kill(no_restart=False)  # crash: the head restarts it
    # wait for the restart as an EVENT (the head's count of this actor's
    # restarts rises), then for ALIVE: the state alone also reads ALIVE
    # before the kill has registered
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        record = svc._record()
        if (record.restarts_used > restarts
                and record.state == ActorState.ALIVE):
            break
        time.sleep(0.1)
    assert record.restarts_used == restarts + 1
    assert record.state == ActorState.ALIVE
    assert ds.to_arrow().num_rows == 6_000
    assert _reexecuted() - before == 0


def test_service_fetch_serves_block_bytes(session):
    """The actor-protocol block_fetch (what ``service_addr`` readers use
    cross-host) serves the same bytes a local read maps."""
    ds = _materialized(session, rows=2_000, parts=1)
    ref = ds.blocks[0]
    meta = store._lookup(ref, fresh=True)
    sock = session.block_service._record().sock_path
    data = bs.service_block_fetch(sock, meta["shm_name"], 0, meta["size"])
    assert data == store.get_bytes(ref)
    assert obs.metrics.counter("block_service.fetches").value >= 0


# ---------------------------------------------------------------------------
# A/B: conf OFF restores PR 8 behavior
# ---------------------------------------------------------------------------


def test_conf_off_restores_executor_ownership_and_lineage():
    """store.block_service=false: no service actor, executor-owned blocks,
    and an executor SIGKILL recovers via lineage re-execution — PR 8
    behavior, byte-for-byte."""
    raydp_tpu.stop_etl()
    s = raydp_tpu.init_etl(
        "test-blocksvc-off", num_executors=2, executor_cores=1,
        executor_memory="300M", configs={"store.block_service": "false"},
    )
    try:
        assert s.block_service is None
        ds = _materialized(s)
        exec_ids = {h._actor_id for h in s.executors}
        owners = {store.owner_of(b) for b in ds.blocks}
        assert owners <= exec_ids, (owners, exec_ids)
        df = dataset_to_dataframe(s, ds)
        clean = df.group_by("k").count().sort("k").collect()
        before = _reexecuted()
        victim = chaos.block_owner_executor(s, ds)
        chaos.kill_executor(s, handle=victim)
        time.sleep(0.3)
        assert df.group_by("k").count().sort("k").collect() == clean
        assert _reexecuted() - before >= 1
        # and scale-in re-owns to the master exactly as before
        before_reown = obs.metrics.counter(
            "rpc.client.calls.object_reown_all"
        ).value
        s.request_total_executors(2)
        s.kill_executors(1, min_keep=1)
        assert (
            obs.metrics.counter("rpc.client.calls.object_reown_all").value
            - before_reown
            >= 1
        )
    finally:
        raydp_tpu.stop_etl()


# ---------------------------------------------------------------------------
# dead service: lineage fallback + dead-owner fast path
# ---------------------------------------------------------------------------


def test_dead_service_falls_back_to_lineage(session):
    """Killing the SERVICE (no restart) is real loss — the head tombstones
    and unlinks every service-owned block — and queries recover via
    lineage re-execution, byte-identical."""
    ds = _materialized(session)
    df = dataset_to_dataframe(session, ds)
    clean = df.group_by("k").count().sort("k").collect()
    before = _reexecuted()
    chaos.kill_service(session)
    time.sleep(0.3)
    assert df.group_by("k").count().sort("k").collect() == clean
    assert _reexecuted() - before >= 1


def test_dead_service_fastpath_zero_head_rpcs(session):
    """A stale CACHED location owned by the dead service short-circuits to
    OwnerDiedError with ZERO head RPCs — the dead-owner fast path works
    for service owners exactly as it did for executor owners."""
    ds = _materialized(session, rows=500, parts=1)
    ref = ds.blocks[0]
    service_id = session.block_service._actor_id
    assert T.read_table_block(ref).num_rows == 500  # warm the cache
    meta = store.cached_location(ref.object_id)
    assert meta is not None and meta["owner"] == service_id
    shm_name = store._lookup(ref, fresh=True)["shm_name"]

    chaos.kill_service(session)  # notes the dead owner, like a head reply
    deadline = time.monotonic() + 10
    while os.path.exists("/dev/shm" + shm_name):
        assert time.monotonic() < deadline
        time.sleep(0.05)
    assert store.cached_location(ref.object_id) is not None

    calls_before = obs.metrics.counter("rpc.client.calls").value
    fast_before = obs.metrics.counter("store.dead_owner_fastpath").value
    with pytest.raises(OwnerDiedError) as excinfo:
        store.get_buffer(ref)
    assert obs.metrics.counter("rpc.client.calls").value == calls_before
    assert (
        obs.metrics.counter("store.dead_owner_fastpath").value
        == fast_before + 1
    )
    assert getattr(excinfo.value, "object_ids", None) == [ref.object_id]


def test_registrations_fall_back_after_service_death(session):
    """With the service dead, NEW blocks register executor-owned (the
    head's handoff fallback) — never parked on a corpse owner that no
    death event would ever GC."""
    chaos.kill_service(session)
    deadline = time.monotonic() + 10
    while bs.service_for_namespace("") is not None:
        assert time.monotonic() < deadline
        time.sleep(0.05)
    ds = _materialized(session, rows=4_000)
    exec_ids = {h._actor_id for h in session.executors}
    owners = {store.owner_of(b) for b in ds.blocks}
    assert owners <= exec_ids, (owners, exec_ids)


def test_ownership_still_dies_with_session():
    """The parity contract survives the service: non-transferred data dies
    at stop (the service is killed with the session), raising
    OwnerDiedError exactly as executor-owned data did."""
    raydp_tpu.stop_etl()
    s = raydp_tpu.init_etl(
        "test-blocksvc-stop", num_executors=2, executor_cores=1,
        executor_memory="300M",
    )
    ds = _materialized(s, rows=1_000)
    assert store.owner_of(ds.blocks[0]) == s.block_service._actor_id
    raydp_tpu.stop_etl()
    store.evict_location(ds.blocks[0].object_id)
    with pytest.raises((OwnerDiedError, ClusterError)):
        cluster.head_rpc("object_lookup", object_id=ds.blocks[0].object_id)


# ---------------------------------------------------------------------------
# RPC robustness: the block-fetch retry ladder
# ---------------------------------------------------------------------------


def test_fetch_retry_ladder_counts_and_degrades(monkeypatch):
    """A fetch against an unreachable block server retries with jittered
    backoff (counted ``rpc.retries``) and, past the per-call deadline,
    raises a lost-block-SHAPED ClusterError (``object_ids`` attached,
    counted ``rpc.deadline_exceeded``) — the reader degrades to lineage
    recovery instead of seeing a raw ConnectionRefusedError."""
    ref = store.ObjectRef("feedfacefeedface", 8)
    meta = {
        "shm_name": "/rtpu-nope", "size": 8, "owner": "gone",
        "node_id": "n", "shm_ns": "other-ns",
        "fetch_addr": "tcp://127.0.0.1:9",  # nothing listens: refused
    }
    monkeypatch.setenv(store.FETCH_DEADLINE_ENV, "0.4")
    # pin re-resolution to the same dead location: the ladder itself is
    # under test, not the head's authoritative answer
    monkeypatch.setattr(
        store, "_lookup", lambda r, fresh=False: dict(meta)
    )
    retries_before = obs.metrics.counter("rpc.retries").value
    deadline_before = obs.metrics.counter("rpc.deadline_exceeded").value
    t0 = time.monotonic()
    with pytest.raises(ClusterError) as excinfo:
        store._remote_fetch(ref, dict(meta), 0, 8)
    assert time.monotonic() - t0 < 10  # bounded, not hung
    assert getattr(excinfo.value, "object_ids", None) == [ref.object_id]
    assert not isinstance(excinfo.value, OwnerDiedError)
    assert obs.metrics.counter("rpc.retries").value > retries_before
    assert (
        obs.metrics.counter("rpc.deadline_exceeded").value
        == deadline_before + 1
    )


def test_fetch_ladder_does_not_retry_gone_segment(monkeypatch):
    """A remote 'segment/file is gone' (FileNotFoundError) is NOT
    transient — the bytes are gone while the meta survives — so the ladder
    surfaces it immediately instead of stalling the reader for the whole
    deadline against the same answer."""
    import socketserver
    import threading

    from raydp_tpu.cluster.common import recv_frame, send_frame

    class Handler(socketserver.BaseRequestHandler):
        def handle(self):
            recv_frame(self.request)
            send_frame(self.request, ("err", FileNotFoundError(2, "gone")))

    sock_path = os.path.join("/tmp", f"bs-gone-{os.getpid()}.sock")
    try:
        os.unlink(sock_path)
    except OSError:
        pass
    server = socketserver.ThreadingUnixStreamServer(sock_path, Handler)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        ref = store.ObjectRef("0123456789abcdef", 8)
        meta = {
            "shm_name": "/rtpu-gone", "size": 8, "owner": "svc",
            "node_id": "n", "shm_ns": "other-ns",
            "fetch_addr": sock_path, "service_addr": sock_path,
        }
        monkeypatch.setenv(store.FETCH_DEADLINE_ENV, "30")
        retries_before = obs.metrics.counter("rpc.retries").value
        t0 = time.monotonic()
        with pytest.raises(FileNotFoundError):
            store._remote_fetch(ref, dict(meta), 0, 8)
        assert time.monotonic() - t0 < 5  # immediate, not the deadline
        assert obs.metrics.counter("rpc.retries").value == retries_before
    finally:
        server.shutdown()
        server.server_close()


def test_fetch_ladder_reresolves_service_restart(monkeypatch):
    """Mid-ladder re-resolution: when the head's fresh location points at a
    LIVE server (the service restarted onto a new socket), the fetch
    completes instead of timing out — a bouncing service costs backoff,
    not failure."""
    import socketserver
    import threading

    from raydp_tpu.cluster.common import recv_frame, send_frame

    payload = b"restored!"

    class Handler(socketserver.BaseRequestHandler):
        def handle(self):
            recv_frame(self.request)
            send_frame(self.request, ("ok", payload))

    server = socketserver.ThreadingUnixStreamServer(
        os.path.join("/tmp", f"bs-restart-{os.getpid()}.sock"), Handler
    )
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        live_addr = server.server_address
        ref = store.ObjectRef("cafebabecafebabe", len(payload))
        dead = {
            "shm_name": "/rtpu-x", "size": len(payload), "owner": "svc",
            "node_id": "n", "shm_ns": "other-ns",
            "fetch_addr": "tcp://127.0.0.1:9",
            "service_addr": "tcp://127.0.0.1:9",
        }
        live = dict(dead, service_addr=live_addr)
        monkeypatch.setenv(store.FETCH_DEADLINE_ENV, "20")
        monkeypatch.setattr(
            store, "_lookup", lambda r, fresh=False: dict(live)
        )
        out = store._remote_fetch(ref, dict(dead), 0, len(payload))
        assert out == payload
    finally:
        server.shutdown()
        server.server_close()
