"""Cross-host data plane tests (store/block_service.py pooled streaming
transport, docs/cluster.md "Multi-host topology"):

- N sequential fetches against one service reuse ONE pooled socket
  (the per-fetch TCP handshake regression this pool exists to kill);
- idle pooled connections age out past RAYDP_TPU_FETCH_POOL_IDLE_S;
- a pooled socket whose peer died is probed and evicted, never reused;
- ``into=`` lands the raw-streamed bytes directly in the caller's buffer,
  and the non-streaming fallback (RAYDP_TPU_STREAM_FETCH=0) serves the
  same bytes;
- the retry ladder re-resolves to a RELOCATED service socket (restart on
  a new port mid-fetch) over the pooled transport;
- a service-side FileNotFoundError fast-fails through the pool AND leaves
  the pooled connection clean for the next caller;
- the topology host axis: node records and location metas carry ``host``,
  and remote fetches count ``rpc.bytes_over_wire{src,dst}``.
"""

import os
import socketserver
import threading
import time

import pytest

import raydp_tpu
from raydp_tpu import obs
from raydp_tpu.cluster import api as cluster
from raydp_tpu.cluster.common import (
    ActorState,
    host_id,
    host_label,
    recv_frame,
    send_frame,
)
from raydp_tpu.etl import functions as F
from raydp_tpu.exchange import dataframe_to_dataset
from raydp_tpu.store import block_service as bs
from raydp_tpu.store import object_store as store


@pytest.fixture()
def session(monkeypatch):
    # TCP sockets for every actor: the head only advertises a service's
    # ``service_addr`` when it is remotely reachable (tcp://), which is
    # what these transport tests exercise
    monkeypatch.setenv("RAYDP_TPU_TCP", "1")
    # a cluster that an earlier module of this worker left up (test_chaos,
    # test_block_service) was booted without the variable and advertises no
    # service_addr: start from none
    cluster.shutdown()
    s = raydp_tpu.init_etl(
        "test-xhost", num_executors=2, executor_cores=1,
        executor_memory="300M",
    )
    yield s
    raydp_tpu.stop_etl()
    # the cluster (head + zygote) booted under RAYDP_TPU_TCP=1 — tear it
    # down so later modules don't fork actors from a TCP-mode zygote
    cluster.shutdown()


def _materialized(session, rows=4_000, parts=1):
    src = session.range(rows, num_partitions=parts).with_column(
        "k", F.col("id") % 7
    )
    return dataframe_to_dataset(src)


def _service_meta(session):
    ds = _materialized(session)
    ref = ds.blocks[0]
    meta = store._lookup(ref, fresh=True)
    assert meta.get("service_addr"), meta
    return ref, meta


# ---------------------------------------------------------------------------
# the connection pool
# ---------------------------------------------------------------------------


def test_pool_reuses_connections(session):
    """Regression for the per-fetch TCP connection: N sequential fetches
    to one service must ride ≤ pool-size sockets — here exactly one."""
    ref, meta = _service_meta(session)
    addr = meta["service_addr"]
    expected = store.get_bytes(ref)
    n = 12
    before = bs.service_pool_stats()
    for _ in range(n):
        data = bs.service_block_fetch(addr, meta["shm_name"], 0, meta["size"])
        assert bytes(data) == expected
    after = bs.service_pool_stats()
    opened = after["connections_opened"] - before["connections_opened"]
    assert opened <= 1, (before, after)
    assert after["reuses"] - before["reuses"] >= n - 1


def test_pool_idle_timeout_evicts(session, monkeypatch):
    """A pooled connection older than the idle cut is closed on the next
    acquire instead of being handed out."""
    monkeypatch.setenv(bs.POOL_IDLE_ENV, "0.05")
    ref, meta = _service_meta(session)
    addr = meta["service_addr"]
    bs.service_block_fetch(addr, meta["shm_name"], 0, meta["size"])
    time.sleep(0.15)
    before = bs.service_pool_stats()
    bs.service_block_fetch(addr, meta["shm_name"], 0, meta["size"])
    after = bs.service_pool_stats()
    assert after["evicted_idle"] - before["evicted_idle"] >= 1
    assert after["connections_opened"] - before["connections_opened"] >= 1


def test_pool_probes_out_dead_peers():
    """A pooled socket whose peer has gone away reads as EOF on the
    zero-timeout probe and is evicted (``evicted_stale``), never reused —
    a one-shot server that closes after each reply makes every pooled
    entry stale by construction."""

    class OneShot(socketserver.BaseRequestHandler):
        def handle(self):
            recv_frame(self.request)
            send_frame(self.request, ("ok", b"x" * 8))

    sock_path = os.path.join("/tmp", f"bs-oneshot-{os.getpid()}.sock")
    try:
        os.unlink(sock_path)
    except OSError:
        pass
    server = socketserver.ThreadingUnixStreamServer(sock_path, OneShot)
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        before = bs.service_pool_stats()
        for _ in range(3):
            out = bs.service_block_fetch(sock_path, "/x", 0, 8)
            assert bytes(out) == b"x" * 8
            time.sleep(0.05)  # let the server-side close land in the pool
        after = bs.service_pool_stats()
        # first fetch opens; the pooled (now closed) socket is probed out
        # on each later acquire, forcing a fresh connect every time
        assert after["evicted_stale"] - before["evicted_stale"] >= 2
        assert after["connections_opened"] - before["connections_opened"] == 3
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------------
# zero-copy landing + A/B fallback
# ---------------------------------------------------------------------------


def test_raw_stream_lands_in_caller_buffer(session):
    """``into=`` receives the raw-framed reply directly into the caller's
    destination — the path the parallel chunked fetch assembles on."""
    ref, meta = _service_meta(session)
    expected = store.get_bytes(ref)
    buf = bytearray(meta["size"])
    n = bs.service_block_fetch(
        meta["service_addr"], meta["shm_name"], 0, meta["size"],
        into=memoryview(buf),
    )
    assert n == meta["size"]
    assert bytes(buf) == expected


def test_stream_fetch_off_serves_same_bytes(session, monkeypatch):
    """RAYDP_TPU_STREAM_FETCH=0 drops to the pickled ``block_fetch`` reply
    over the same pooled socket — byte-identical."""
    ref, meta = _service_meta(session)
    expected = store.get_bytes(ref)
    monkeypatch.setenv(bs.STREAM_FETCH_ENV, "0")
    data = bs.service_block_fetch(
        meta["service_addr"], meta["shm_name"], 0, meta["size"]
    )
    assert bytes(data) == expected


# ---------------------------------------------------------------------------
# retry ladder over the pooled transport
# ---------------------------------------------------------------------------


def test_ladder_reresolves_relocated_service(session, monkeypatch):
    """The service restarts onto a NEW port; a reader holding the stale
    location retries the refused old socket, re-resolves mid-ladder, and
    completes against the relocated service — over the pooled transport."""
    ref, meta = _service_meta(session)
    expected = store.get_bytes(ref)
    stale = dict(meta)
    old_addr = stale["service_addr"]
    svc = session.block_service
    svc.kill(no_restart=False)
    deadline = time.monotonic() + 15
    new_addr = old_addr
    while time.monotonic() < deadline:
        if svc.state() == ActorState.ALIVE:
            new_addr = svc._record().sock_path
            if new_addr != old_addr:
                break
        time.sleep(0.1)
    assert svc.state() == ActorState.ALIVE
    assert new_addr != old_addr, "restart did not relocate the socket"
    monkeypatch.setenv(store.FETCH_DEADLINE_ENV, "30")
    t0 = time.monotonic()
    out = store._remote_fetch(ref, stale, 0, meta["size"])
    assert time.monotonic() - t0 < 25
    assert bytes(out) == expected


def test_filenotfound_fast_fails_and_pool_stays_clean(session, monkeypatch):
    """A service-side FileNotFoundError (segment gone, meta alive) is not
    transient: the ladder re-raises it immediately. The error reply is a
    fully-consumed frame, so the pooled connection is RELEASED clean and
    the very next fetch reuses it instead of reconnecting."""
    ref, meta = _service_meta(session)
    bogus = dict(meta, shm_name="/rtpu-definitely-not-here")
    monkeypatch.setenv(store.FETCH_DEADLINE_ENV, "30")
    before = bs.service_pool_stats()
    t0 = time.monotonic()
    with pytest.raises(FileNotFoundError):
        store._remote_fetch(ref, bogus, 0, meta["size"])
    assert time.monotonic() - t0 < 5  # immediate, not the 30s deadline
    data = bs.service_block_fetch(
        meta["service_addr"], meta["shm_name"], 0, meta["size"]
    )
    assert bytes(data) == store.get_bytes(ref)
    after = bs.service_pool_stats()
    assert after["connections_opened"] - before["connections_opened"] <= 1
    assert after["reuses"] - before["reuses"] >= 1


# ---------------------------------------------------------------------------
# topology: the host axis
# ---------------------------------------------------------------------------


def test_nodes_and_metas_carry_host(session):
    """Every node record and location meta names its host (real boxes set
    RAYDP_TPU_HOST_ID; the head's virtual nodes share the head's own host,
    where the empty string IS the identity) — the axis locality scoring
    and wire accounting key on."""
    for node in cluster.nodes():
        assert node.host == host_id(), node
    ref, meta = _service_meta(session)
    assert "host" in meta, meta
    assert meta["host"] == host_id()


def test_remote_fetch_counts_bytes_over_wire(session):
    """A fetch served over the service socket from another host counts
    ``rpc.remote_fetches`` and the ``rpc.bytes_over_wire`` aggregate plus
    its per-edge {src_host, dst_host} counter."""
    ref, meta = _service_meta(session)
    faraway = dict(meta, shm_ns="simhostB", host="simhostB")
    src, dst = host_label("simhostB"), host_label(host_id())
    edge = obs.metrics.counter(f"rpc.bytes_over_wire.{src}.{dst}")
    total = obs.metrics.counter("rpc.bytes_over_wire")
    fetches = obs.metrics.counter("rpc.remote_fetches")
    before = (total.value, edge.value, fetches.value)
    out = store._remote_fetch(ref, faraway, 0, meta["size"])
    assert bytes(out) == store.get_bytes(ref)
    assert total.value - before[0] >= meta["size"]
    assert edge.value - before[1] >= meta["size"]
    assert fetches.value - before[2] >= 1
