"""SPMD job launcher — generic multi-process SPMD on the cluster runtime.

Re-architecture of the reference's MPI-on-Ray (SURVEY.md P14-P16, §3.5):
where the reference reserves hosts with a STRICT_SPREAD placement group,
launches real ``mpirun``, and wires a gRPC control plane for function
shipping (mpi_job.py:165-278), here the ranks ARE actors on the cluster
runtime — the control plane is the actor RPC itself, and the *data plane for
gradients doesn't exist at this layer at all*: ranks bootstrap
``jax.distributed`` and collectives compile into their jitted step functions
over ICI/DCN. Kept semantics: one rank per placement bundle (spread), strict
function-id ordering per rank (mpi_worker.py TaskRunner :75-96), fan-out
run + gather results in rank order (mpi_job.py:325-339), restartable
start/stop/reset (:345-396).
"""

from __future__ import annotations

import threading
import uuid
from typing import Any, Callable, Dict, List, Optional

import cloudpickle

from raydp_tpu.cluster import api as cluster


class WorkerContext:
    """Passed to every shipped function (parity: mpi WorkerContext)."""

    def __init__(self, job_name: str, rank: int, world_size: int):
        self.job_name = job_name
        self.rank = rank
        self.world_size = world_size

    def __repr__(self):
        return f"WorkerContext({self.job_name}, rank={self.rank}/{self.world_size})"


class SpmdWorker:
    """One rank: executes shipped functions in submission order. The job env
    (incl. rank/world vars) arrives via the actor's process environment —
    set at spawn so interpreter-startup consumers (JAX platform selection)
    see it; nothing is re-applied here."""

    def __init__(self, job_name: str, rank: int, world_size: int):
        from raydp_tpu.sanitize import named_lock

        self.ctx = WorkerContext(job_name, rank, world_size)
        self._next_func_id = 0
        self._lock = named_lock("spmd.worker")

    def ping(self) -> int:
        return self.ctx.rank

    def pick_free_port(self) -> int:
        """A free TCP port on THIS rank's host (the jax.distributed
        coordinator must bind where rank 0 actually runs)."""
        import socket

        with socket.socket() as s:
            s.bind(("0.0.0.0", 0))
            return s.getsockname()[1]

    def bootstrap_jax_distributed(
        self, coordinator_address: str, num_processes: int, process_id: int
    ) -> int:
        """Join the jax.distributed mesh (the reference's analog: each mpi
        rank joins Ray via ray.init(address), mpi_worker.py:158-166)."""
        import jax

        from raydp_tpu.compile_cache import enable_compile_cache

        enable_compile_cache()
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
        return len(jax.devices())

    def run_function(self, func_id: int, blob: bytes) -> Any:
        """Execute a shipped function. Strict ordering: func_id must be the
        next expected (parity: mpi_worker TaskRunner check, :85-90)."""
        with self._lock:
            if func_id != self._next_func_id:
                raise RuntimeError(
                    f"out-of-order function: got {func_id}, expected {self._next_func_id}"
                )
            self._next_func_id += 1
        fn = cloudpickle.loads(blob)
        return fn(self.ctx)


class SpmdJob:
    def __init__(
        self,
        job_name: str,
        world_size: int,
        num_cpus_per_worker: float = 1.0,
        placement_group: Optional[cluster.PlacementGroup] = None,
        placement_group_bundle_indexes: Optional[List[int]] = None,
        placement_strategy: str = "SPREAD",
        env: Optional[Dict[str, str]] = None,
        timeout: float = 120.0,
    ):
        self.job_name = job_name
        self.world_size = world_size
        self.num_cpus_per_worker = num_cpus_per_worker
        self.placement_strategy = placement_strategy
        self.env = dict(env or {})
        self.timeout = timeout
        self._pg = placement_group
        self._bundle_indexes = placement_group_bundle_indexes
        self._owns_pg = False
        self._workers: List[cluster.ActorHandle] = []
        self._func_id = 0
        self._started = False
        from raydp_tpu.sanitize import named_lock

        self._lock = named_lock("spmd.job", threading.RLock())

    # ------------------------------------------------------------------

    def start(self) -> "SpmdJob":
        """Reserve one bundle per rank (spread across nodes like the mpi
        launcher's STRICT_SPREAD peers, mpi_job.py:192-222) and spawn ranks."""
        with self._lock:
            if self._started:
                raise RuntimeError(f"job {self.job_name} already started")
            if not cluster.is_initialized():
                cluster.init()
            if self._pg is None:
                bundles = [
                    {"CPU": float(self.num_cpus_per_worker)}
                    for _ in range(self.world_size)
                ]
                try:
                    self._pg = cluster.create_placement_group(
                        bundles, strategy=self.placement_strategy
                    )
                except Exception:
                    # resources are logical: grow the cluster with an extra
                    # node rather than failing (an ETL session may be holding
                    # the original CPUs — the reference runs Ray Train worker
                    # groups beside Spark executors the same way)
                    cluster.add_node(
                        {
                            "CPU": float(self.num_cpus_per_worker)
                            * self.world_size,
                            "memory": float(1 << 30),
                        }
                    )
                    self._pg = cluster.create_placement_group(
                        bundles, strategy=self.placement_strategy
                    )
                self._owns_pg = True
            indexes = self._bundle_indexes or list(range(self.world_size))
            self._workers = []
            try:
                for rank in range(self.world_size):
                    handle = cluster.spawn(
                        SpmdWorker,
                        self.job_name,
                        rank,
                        self.world_size,
                        name=f"{self.job_name}-rank-{rank}",
                        num_cpus=self.num_cpus_per_worker,
                        placement_group=self._pg.id,
                        bundle_index=indexes[rank % len(indexes)],
                        max_restarts=0,
                        max_concurrency=2,
                        # env must be in place at process start: platform
                        # selection (JAX_PLATFORMS/XLA_FLAGS) is read during
                        # interpreter startup, before __init__ runs
                        env={
                            **self.env,
                            "RAYDP_TPU_SPMD_RANK": str(rank),
                            "RAYDP_TPU_SPMD_WORLD_SIZE": str(self.world_size),
                        },
                        block=False,
                    )
                    self._workers.append(handle)
                for handle in self._workers:
                    handle.wait_ready(timeout=self.timeout)
            except BaseException:
                # don't leak actors/PG when a rank fails to come up: the
                # caller never gets a handle to stop()
                self._started = True  # let stop() run its full path
                self.stop()
                raise
            self._started = True
            return self

    def _worker_host(self, rank: int) -> str:
        """The given rank's node address from its actor record — never the
        driver's loopback: ranks placed on other machines must reach it."""
        try:
            record = self._workers[rank]._record()
            return record.node_ip if record and record.node_ip else "127.0.0.1"
        except Exception:
            return "127.0.0.1"

    def _worker_host_port(self, rank: int, port: int = 0) -> str:
        """``host:port`` on the given rank's node; the port is picked ON the
        rank's host (the driver cannot probe another machine's port space)."""
        if port == 0:
            port = self._workers[rank].pick_free_port.options(
                timeout=self.timeout
            ).remote().result()
        return f"{self._worker_host(rank)}:{port}"

    def rendezvous_address(self, port: int = 0) -> str:
        """``host:port`` on RANK 0's node, for any single-coordinator
        worker-group rendezvous (jax.distributed coordinator, torch gloo
        store, ...). Ray Train plays this role for the reference's
        estimators (torch/estimator.py:311-327)."""
        return self._worker_host_port(0, port)

    def worker_addresses(self) -> List[str]:
        """One reachable ``host:port`` per rank (each port picked on that
        rank's own host) — the cluster spec an all-workers rendezvous like
        TF's ``TF_CONFIG`` needs. Port picks fan out concurrently: serial
        round trips would cost 2·world_size control-plane RTTs per fit."""
        futures = [
            w.pick_free_port.options(timeout=self.timeout).remote()
            for w in self._workers
        ]
        return [
            f"{self._worker_host(rank)}:{f.result()}"
            for rank, f in enumerate(futures)
        ]

    def bootstrap_jax(self, coordinator_port: int = 0) -> List[int]:
        """Bring up jax.distributed across all ranks; returns per-rank global
        device counts. The coordinator binds on RANK 0's node — its address
        is resolved from rank 0's actor record, not the driver's loopback,
        so multi-host jobs rendezvous correctly (round-1 ADVICE: the old
        127.0.0.1 address silently broke off the driver's host)."""
        address = self.rendezvous_address(coordinator_port)
        futures = [
            w.bootstrap_jax_distributed.options(timeout=self.timeout).remote(
                address, self.world_size, rank
            )
            for rank, w in enumerate(self._workers)
        ]
        return [f.result() for f in futures]

    def run(self, fn: Callable[[WorkerContext], Any], timeout: Optional[float] = None) -> List[Any]:
        """Ship ``fn`` to every rank concurrently; gather in rank order
        (parity: mpi_job.run, :325-339).

        The gather FAILS FAST: a dead rank surfaces immediately instead of
        waiting out rank 0 first — with collectives in flight, surviving
        ranks hang on the dead one, so rank-order result() would stall the
        whole deadline before reporting the failure. The elastic watchdog
        depends on this to restart gangs promptly."""
        import time

        with self._lock:
            if not self._started:
                raise RuntimeError("job not started")
            func_id = self._func_id
            self._func_id += 1
        blob = cloudpickle.dumps(fn)
        wait = self.timeout if timeout is None else timeout
        futures = [
            w.run_function.options(timeout=wait).remote(func_id, blob)
            for w in self._workers
        ]
        import selectors

        results: List[Any] = [None] * len(futures)
        done = [False] * len(futures)
        for i, future in enumerate(futures):
            if getattr(future, "_sock", None) is None:  # already-completed
                results[i] = future.result()
                done[i] = True
        deadline = time.monotonic() + wait
        # Readable sockets are drained on worker THREADS: result() reads a
        # whole frame under the actor timeout, so one rank streaming a large
        # or partial frame must not stall detection of other ranks' failures
        # (the sweep's constant-latency guarantee — the elastic watchdog
        # depends on it).
        import queue

        drain_q: "queue.Queue" = queue.Queue()
        draining: set = set()

        def _drain(idx, fut):
            try:
                drain_q.put((idx, fut.result(), None))
            except BaseException as e:  # noqa: BLE001 — relayed to the sweep
                drain_q.put((idx, None, e))

        while not all(done):
            # ONE poll over every pending rank's socket: sweep latency is
            # constant, not world_size × probe (a dead rank must surface
            # immediately — the elastic watchdog depends on it). selectors
            # (epoll) rather than select(): a long-lived driver can hold
            # fds >= FD_SETSIZE, which select() rejects outright.
            pending = [
                (i, f) for i, f in enumerate(futures)
                if not done[i] and i not in draining
                and getattr(f, "_sock", None) is not None
            ]
            if pending:
                with selectors.DefaultSelector() as sel:
                    for i, f in pending:
                        sel.register(f._sock, selectors.EVENT_READ, i)
                    ready = {key.data for key, _ in sel.select(timeout=0.2)}
                for i, future in pending:
                    if i not in ready:
                        continue
                    draining.add(i)
                    threading.Thread(
                        target=_drain, args=(i, future), daemon=True
                    ).start()
            # harvest finished drains (block briefly only when every pending
            # rank is already mid-drain, so the loop still ticks the deadline)
            block = not pending
            while True:
                try:
                    i, value, err = drain_q.get(
                        timeout=0.2 if block else 0.0
                    )
                except queue.Empty:  # raydp-lint: disable=swallowed-exceptions (queue drain)
                    break
                block = False
                draining.discard(i)
                if err is not None:
                    # rank failure (remote raise / ConnectionError /
                    # ActorDiedError): fail fast
                    raise err
                results[i] = value
                done[i] = True
            if not all(done) and time.monotonic() > deadline:
                raise TimeoutError(
                    f"spmd job {self.job_name}: "
                    f"{done.count(False)} rank(s) did not finish within {wait}s"
                )
        return results

    def stop(self) -> None:
        import time

        from raydp_tpu.cluster.common import ActorState

        # The whole teardown runs UNDER the job lock ON PURPOSE: stop() is
        # only "done" once the ranks are DEAD and the PG's bundles are back,
        # and a start() admitted mid-drain would see self._pg already None,
        # fail to create a new PG against the still-reserved bundles, and
        # fall into its add_node() fallback — permanently growing the
        # cluster. The lock is the job's lifecycle serializer, its hold is
        # bounded by the 15s drain deadline, and nothing under it takes any
        # other instrumented lock, so no inversion is possible.
        with self._lock:
            killed = list(self._workers)
            for w in killed:
                try:
                    w.kill(no_restart=True)
                except Exception:
                    # already dead is the common case; count the rest so a
                    # systematically failing teardown is visible in metrics
                    from raydp_tpu.obs import metrics

                    metrics.counter("spmd.teardown_kill_failures").inc()
            self._workers = []
            # drain: bundles must be free before the PG is removed, and the
            # next job's PG must see the resources back
            deadline = time.monotonic() + 15.0
            for w in killed:
                while time.monotonic() < deadline:
                    try:
                        if w.state() == ActorState.DEAD:
                            break
                    except Exception:  # raydp-lint: disable=swallowed-exceptions (polling a dying actor)
                        break
                    # raydp-lint: disable=blocking-under-lock (deliberate, deadline-bounded hold — see the lifecycle-serializer comment above)
                    time.sleep(0.05)
            if self._owns_pg and self._pg is not None:
                try:
                    cluster.remove_placement_group(self._pg)
                except Exception:
                    from raydp_tpu.obs import log as obs_log

                    obs_log.warning(
                        "failed to remove SPMD placement group; bundles may "
                        "stay reserved until session shutdown",
                        pg=self._pg.id, exc_info=True,
                    )
                self._pg = None
                self._owns_pg = False
            self._started = False
            self._func_id = 0

    # restart parity (reference _reset + start again, :345-396)
    def restart(self) -> "SpmdJob":
        self.stop()
        return self.start()

    def __enter__(self) -> "SpmdJob":
        return self.start() if not self._started else self

    def __exit__(self, *exc) -> None:
        self.stop()


def create_spmd_job(
    job_name: Optional[str] = None,
    world_size: int = 1,
    num_cpus_per_worker: float = 1.0,
    placement_group: Optional[cluster.PlacementGroup] = None,
    placement_group_bundle_indexes: Optional[List[int]] = None,
    placement_strategy: str = "SPREAD",
    env: Optional[Dict[str, str]] = None,
    timeout: float = 120.0,
) -> SpmdJob:
    """Parity: raydp.mpi.create_mpi_job (reference mpi/__init__.py:36-91)."""
    return SpmdJob(
        job_name or f"spmd-{uuid.uuid4().hex[:8]}",
        world_size,
        num_cpus_per_worker=num_cpus_per_worker,
        placement_group=placement_group,
        placement_group_bundle_indexes=placement_group_bundle_indexes,
        placement_strategy=placement_strategy,
        env=env,
        timeout=timeout,
    )
