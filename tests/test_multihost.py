"""Multi-host substrate tests: TCP transport, per-node shm namespaces,
cross-node block pull, locality-aware dispatch.

A node agent with its own shm NAMESPACE stands in for a second host (round-1
VERDICT item 1): its blocks cannot be mapped by other nodes' processes, so
every cross-node read must travel the same TCP pull path a real multi-host
deployment uses. Parity targets: Ray multi-node actors + plasma pulls
(SURVEY.md L1), RayDatasetRDD.getPreferredLocations locality
(reference core/.../RayDatasetRDD.scala:53-55).
"""

import numpy as np
import pandas as pd
import pyarrow as pa
import pytest

from raydp_tpu.cluster import api as cluster
from raydp_tpu.cluster.common import rpc
from raydp_tpu.etl import plan as lp
from raydp_tpu.etl import tasks as T
from raydp_tpu.etl.executor import EtlExecutor
from raydp_tpu.etl.planner import Planner
from raydp_tpu.store import object_store as store


@pytest.fixture(scope="module")
def two_nodes():
    """A head node plus an agent-backed node with its own shm namespace,
    with one ETL executor pinned to each."""
    cluster.init(num_cpus=4, memory=4 << 30)
    info = cluster.start_node_agent(
        {"CPU": 4.0, "memory": float(2 << 30)}, shm_ns="tnb"
    )
    agent_node = next(
        n for n in cluster.nodes() if n.node_id == info["node_id"]
    )
    head_node = next(
        n for n in cluster.nodes() if n.agent_addr is None
    )
    ex_head = cluster.spawn(
        EtlExecutor, 0, "mh", {},
        name="mh-exec-head", num_cpus=1,
        resources={f"node:{head_node.node_ip}": 0.001},
        max_restarts=1, max_concurrency=3, light=True,
    )
    ex_agent = cluster.spawn(
        EtlExecutor, 1, "mh", {},
        name="mh-exec-agent", num_cpus=1,
        resources={f"node:{agent_node.node_ip}": 0.001},
        max_restarts=1, max_concurrency=3, light=True,
    )
    yield {
        "agent": info,
        "agent_node": agent_node,
        "head_node": head_node,
        "executors": [ex_head, ex_agent],
    }
    for h in (ex_head, ex_agent):
        try:
            h.kill()
        except Exception:
            pass


def _agent_stats(info):
    return rpc(info["addr"], ("stats", {}), timeout=10)


def test_actor_runs_on_agent_node_with_own_namespace(two_nodes):
    rec = two_nodes["executors"][1]._record()
    assert rec.node_id == two_nodes["agent_node"].node_id
    assert rec.sock_path.startswith("tcp://")  # cross-host reachable
    assert two_nodes["agent_node"].shm_ns == "tnb"


def test_cross_node_shuffle_query(two_nodes):
    """A hash-shuffle groupby across two separate-shm nodes: map outputs
    land in each node's own namespace, reducers pull the foreign halves
    over TCP, and the result matches pandas exactly."""
    rng = np.random.default_rng(3)
    pdf = pd.DataFrame(
        {"k": rng.integers(0, 13, 4000), "v": rng.standard_normal(4000)}
    )
    table = pa.Table.from_pandas(pdf, preserve_index=False)
    blocks = []
    for i in range(4):
        ref, _ = T.write_table_block(table.slice(i * 1000, 1000))
        blocks.append(ref)

    planner = Planner(two_nodes["executors"], default_parallelism=4)
    from raydp_tpu.etl import functions as F

    node = lp.GroupByAgg(
        lp.ArrowSource(blocks, table.schema), ["k"],
        [F.sum("v"), F.count("*")],
    )
    served_before = _agent_stats(two_nodes["agent"])["blocks_served"]
    mat = planner.materialize(node)
    out = pa.concat_tables(
        [T.read_table_block(b) for b in mat.blocks if b is not None]
    ).to_pandas().sort_values("k").reset_index(drop=True)

    exp = (
        pdf.groupby("k").agg(**{"sum(v)": ("v", "sum"), "count": ("v", "size")})
        .reset_index().sort_values("k").reset_index(drop=True)
    )
    np.testing.assert_allclose(out["sum(v)"], exp["sum(v)"], atol=1e-9)
    np.testing.assert_array_equal(out["count"], exp["count"])

    # the node boundary was actually crossed: the agent's block server
    # served shuffle blocks to the head-node reducer
    served_after = _agent_stats(two_nodes["agent"])["blocks_served"]
    assert served_after > served_before


def test_reduce_placement_follows_the_bytes(two_nodes):
    """With an executor on each host, every reducer of a cross-host shuffle
    is placed on the host that holds most of its input bytes: the planner
    counts ``planner.locality_hits`` and no miss."""
    from raydp_tpu import obs
    from raydp_tpu.etl import functions as F

    table = pa.table(
        {"k": np.arange(4000) % 13, "v": np.arange(4000, dtype=np.float64)}
    )
    blocks = [
        T.write_table_block(table.slice(i * 1000, 1000))[0] for i in range(4)
    ]
    hits = obs.metrics.counter("planner.locality_hits")
    misses = obs.metrics.counter("planner.locality_misses")
    hits_before, misses_before = hits.value, misses.value
    planner = Planner(two_nodes["executors"], default_parallelism=4)
    mat = planner.materialize(
        lp.GroupByAgg(lp.ArrowSource(blocks, table.schema), ["k"], [F.sum("v")])
    )
    out = pa.concat_tables(
        [T.read_table_block(b) for b in mat.blocks if b is not None]
    )
    assert out.num_rows == 13
    assert hits.value > hits_before
    assert misses.value == misses_before


def test_cross_node_block_read_and_gc(two_nodes):
    """Blocks produced on the agent node are readable from the driver only
    via the network pull path, and deletes unlink them on the agent's host."""
    import os

    ex_agent = two_nodes["executors"][1]
    table = pa.table({"x": list(range(100))})
    spec = T.TaskSpec(
        reads=[
            T.ReadSpec(
                "inline", inline_ipc=T.table_to_ipc_bytes(table),
                schema_ipc=T.schema_ipc_bytes(table.schema),
            )
        ],
        output=T.OutputSpec("block"),
    )
    result = ex_agent.run_task(spec)
    ref = result.blocks[0]
    meta = cluster.head_rpc("object_lookup", object_id=ref.object_id)
    assert meta["shm_ns"] == "tnb"
    assert meta["node_id"] == two_nodes["agent_node"].node_id

    before = store.stats["remote_fetches"]
    read_back = T.read_table_block(ref)
    assert read_back.column("x").to_pylist() == list(range(100))
    assert store.stats["remote_fetches"] > before  # pulled, not mapped

    shm_path = os.path.join("/dev/shm", meta["shm_name"].lstrip("/"))
    assert os.path.exists(shm_path)  # same machine: visible for the test
    store.delete([ref])
    deadline = __import__("time").monotonic() + 10
    while os.path.exists(shm_path) and __import__("time").monotonic() < deadline:
        __import__("time").sleep(0.05)
    assert not os.path.exists(shm_path)  # agent unlinked its namespace


def test_locality_aware_dispatch(two_nodes):
    """Source-read tasks land on the executor co-located with their blocks
    (getPreferredLocations parity): outputs of a narrow map over node-B
    blocks are produced on node B, without shipping inputs."""
    ex_agent = two_nodes["executors"][1]
    agent_node_id = two_nodes["agent_node"].node_id

    # produce 4 blocks ON the agent node
    refs = []
    table = pa.table({"x": np.arange(1000)})
    for i in range(4):
        spec = T.TaskSpec(
            reads=[
                T.ReadSpec(
                    "inline",
                    inline_ipc=T.table_to_ipc_bytes(table.slice(i * 250, 250)),
                    schema_ipc=T.schema_ipc_bytes(table.schema),
                )
            ],
            output=T.OutputSpec("block"),
        )
        refs.append(ex_agent.run_task(spec).blocks[0])

    planner = Planner(two_nodes["executors"], default_parallelism=4)
    from raydp_tpu.etl.expressions import ColumnRef

    node = lp.Project(
        lp.ArrowSource(refs, table.schema), [("x", ColumnRef("x"))]
    )
    before = store.stats["remote_fetches"]
    mat = planner.materialize(node)
    stage = planner.last_query_stats["stages"][0]
    assert stage["locality_preferred"] == 4  # every task had a preference

    locations = cluster.head_rpc(
        "object_locations",
        object_ids=[b.object_id for b in mat.blocks if b is not None],
    )
    assert set(locations.values()) == {agent_node_id}  # ran where data lives
    assert mat.num_rows == 1000


def test_full_etl_session_spans_nodes(two_nodes):
    """init_etl schedules executors across the head node AND the agent node
    (generic resource scheduling — no special casing), and a real dataframe
    query with joins/groupbys over the two-node pool is exact."""
    import raydp_tpu
    from raydp_tpu.etl import functions as F

    # size executors from LIVE free resources so the second one cannot fit
    # on the head node and must spill to the agent node (other test modules
    # may have grown the head's CPU pool)
    avail = cluster.available_resources()
    head_free = avail[two_nodes["head_node"].node_id].get("CPU", 0.0)
    agent_free = avail[two_nodes["agent_node"].node_id].get("CPU", 0.0)
    # spill requires 2*cores > head_free AND the agent must fit one executor
    cores = int(head_free // 2 + 1)
    if cores > agent_free:
        pytest.skip(
            f"agent node too small ({agent_free}) vs head pool ({head_free}) "
            "to force cross-node executor placement"
        )
    session = raydp_tpu.init_etl(
        "mh-session", num_executors=2, executor_cores=cores,
        executor_memory="300M",
    )
    try:
        exec_nodes = {h._record().node_id for h in session.executors}
        rng = np.random.default_rng(5)
        pdf = pd.DataFrame(
            {
                "k": rng.integers(0, 9, 3000),
                "v": rng.standard_normal(3000).round(4),
            }
        )
        df = session.from_pandas(pdf, num_partitions=6)
        out = (
            df.group_by("k").agg(F.sum("v").alias("s"), F.count("*").alias("n"))
            .sort("k")
            .to_pandas()
        )
        exp = (
            pdf.groupby("k").agg(s=("v", "sum"), n=("v", "size")).reset_index()
        )
        np.testing.assert_allclose(out["s"], exp["s"], atol=1e-9)
        np.testing.assert_array_equal(out["n"], exp["n"])
        # both nodes participated
        assert len(exec_nodes) == 2, exec_nodes
    finally:
        raydp_tpu.stop_etl()


def test_tcp_requires_token_and_sane_shm_names(two_nodes):
    """Unauthenticated TCP peers are dropped before any unpickling, and the
    block servers reject path-traversal segment names."""
    import socket as socketlib

    from raydp_tpu.cluster.common import ClusterError, send_frame, recv_frame

    addr = two_nodes["agent"]["addr"]
    host, _, port = addr[6:].rpartition(":")

    # wrong token → server closes without answering
    raw = socketlib.create_connection((host, int(port)), timeout=5)
    raw.sendall(b"\0" * 32)
    send_frame(raw, ("stats", {}))
    raw.settimeout(2)
    with pytest.raises((ConnectionError, OSError)):
        recv_frame(raw)
    raw.close()

    # proper client: traversal names rejected
    with pytest.raises(ClusterError, match="invalid shm segment"):
        rpc(addr, ("block_fetch", {"shm_name": "../../etc/passwd"}), timeout=5)
    with pytest.raises(ClusterError, match="invalid shm segment"):
        rpc(addr, ("block_fetch", {"shm_name": "/rtpu-x/../../etc/passwd"}), timeout=5)


class _SpillActor:
    """Writes a table block to the DISK tier from whatever node it runs on."""

    def write(self, table_bytes):
        import pyarrow as pa

        with pa.ipc.open_stream(table_bytes) as r:
            table = r.read_all()
        return T.write_table_block(table, storage="disk")


def test_spilled_block_fetched_cross_node(two_nodes):
    """A block spilled to DISK on the agent node is served to the head-node
    driver through the agent's block server — the spill tier participates in
    the cross-node data plane exactly like shm segments."""
    import io

    table = pa.table({"a": np.arange(512, dtype=np.int64)})
    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, table.schema) as w:
        w.write_table(table)

    actor = cluster.spawn(
        _SpillActor, name="mh-spill-writer", num_cpus=0.1,
        resources={f"node:{two_nodes['agent_node'].node_ip}": 0.001},
        light=True,
    )
    try:
        ref, n = actor.write.remote(sink.getvalue()).result()
        assert n == 512
        meta = store._lookup(ref)
        assert meta["shm_name"].startswith("file://")
        assert meta["shm_ns"] == "tnb"  # lives on the agent node

        fetched_before = store.stats["remote_fetches"]
        out = T.read_table_block(ref)
        assert out.equals(table)
        assert store.stats["remote_fetches"] == fetched_before + 1
    finally:
        actor.kill()


def test_spill_aware_locality(two_nodes):
    """Blocks in the agent node's DISK tier still dispatch their consumers
    to that node (ROADMAP r3 #4): the head's location table keys on
    node_id, which the spill tier preserves at registration — proven by the
    query running entirely on the spill-owning node with ZERO cross-node
    block serves (the only way another node could read a namespaced spill
    file is through the agent's block server, and its counter is flat)."""
    from raydp_tpu.etl.expressions import ColumnRef

    agent_node = two_nodes["agent_node"]
    ex_spill = cluster.spawn(
        EtlExecutor, 7, "mh-spill", {},
        name="mh-exec-spill", num_cpus=1,
        resources={f"node:{agent_node.node_ip}": 0.001},
        max_restarts=1, max_concurrency=3, light=True,
        env={"RAYDP_TPU_SHM_CAPACITY": "1"},  # force the disk tier
    )
    try:
        table = pa.table({"x": np.arange(2000)})
        refs = []
        for i in range(4):
            spec = T.TaskSpec(
                reads=[
                    T.ReadSpec(
                        "inline",
                        inline_ipc=T.table_to_ipc_bytes(table.slice(i * 500, 500)),
                        schema_ipc=T.schema_ipc_bytes(table.schema),
                    )
                ],
                output=T.OutputSpec("block"),
            )
            refs.append(ex_spill.run_task(spec).blocks[0])
        # every input block is a SPILLED file on the agent node
        for ref in refs:
            meta = cluster.head_rpc("object_lookup", object_id=ref.object_id)
            assert meta["shm_name"].startswith("file://"), meta["shm_name"]
            assert meta["node_id"] == agent_node.node_id

        planner = Planner(
            [two_nodes["executors"][0], ex_spill], default_parallelism=4
        )
        node = lp.Project(
            lp.ArrowSource(refs, table.schema), [("x", ColumnRef("x"))]
        )
        served_before = _agent_stats(two_nodes["agent"])["blocks_served"]
        mat = planner.materialize(node)
        stage = planner.last_query_stats["stages"][0]
        assert stage["locality_preferred"] == 4  # every task had a preference
        locations = cluster.head_rpc(
            "object_locations",
            object_ids=[b.object_id for b in mat.blocks if b is not None],
        )
        assert set(locations.values()) == {agent_node.node_id}
        assert mat.num_rows == 2000
        # no cross-node pull happened: the spilled inputs were read from
        # the local disk tier by the co-located executor
        assert _agent_stats(two_nodes["agent"])["blocks_served"] == served_before
    finally:
        try:
            ex_spill.kill()
        except Exception:
            pass
