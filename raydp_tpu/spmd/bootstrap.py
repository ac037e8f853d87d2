"""Multi-host mesh bootstrap.

The L1' mesh runtime of SURVEY.md §7: on a TPU pod each host runs one process;
``initialize_from_env`` wires ``jax.distributed`` from the env the SPMD job
launcher (job.py) or an external scheduler provides, after which
``jax.devices()`` spans the pod and ``parallel.make_mesh`` lays ICI/DCN axes.

The reference's analog is the MPI rank discovering itself from OMPI/PMI env
vars and joining Ray (mpi_worker.py:33-42,158-166).
"""

from __future__ import annotations

import os
from typing import Optional

COORD_ENV = "RAYDP_TPU_COORDINATOR"
RANK_ENV = "RAYDP_TPU_SPMD_RANK"
WORLD_ENV = "RAYDP_TPU_SPMD_WORLD_SIZE"


def initialize_from_env(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Idempotent jax.distributed bootstrap from args or env (no-op when
    single-process)."""
    import jax

    coordinator = coordinator_address or os.environ.get(COORD_ENV)
    world = num_processes if num_processes is not None else int(
        os.environ.get(WORLD_ENV, "1")
    )
    rank = process_id if process_id is not None else int(
        os.environ.get(RANK_ENV, "0")
    )
    if world <= 1 or coordinator is None:
        return
    if jax.distributed.is_initialized():
        return
    jax.distributed.initialize(
        coordinator_address=coordinator,
        num_processes=world,
        process_id=rank,
    )


def process_rank() -> int:
    return int(os.environ.get(RANK_ENV, "0"))


def world_size() -> int:
    return int(os.environ.get(WORLD_ENV, "1"))
