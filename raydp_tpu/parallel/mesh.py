"""Device-mesh construction over ICI×DCN axes.

The reference has no model parallelism (SURVEY.md §2.4: TP/PP/SP/EP absent);
its distributed story is DDP over Gloo/NCCL plus mpirun. Here the mesh is the
*single* abstraction all parallelism hangs off: data, fsdp, tensor, sequence
and expert axes are named mesh dimensions, and every collective is compiled
into the step function by XLA — the "pick a mesh, annotate shardings, let XLA
insert collectives" recipe.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import numpy as np


def make_mesh(
    axes: Dict[str, int],
    devices: Optional[Sequence] = None,
    allow_split_physical: bool = True,
):
    """Build a Mesh with named ``axes`` (e.g. {"data": 4, "model": 2}).

    A -1 axis size absorbs the remaining devices (like a reshape). Axis order
    matters on real hardware: earlier axes are outer (DCN-ish), later axes are
    inner (ICI-adjacent) — put tensor/sequence axes last so their collectives
    ride the fastest links.
    """
    import jax
    from jax.sharding import Mesh

    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    names = list(axes.keys())
    sizes = list(axes.values())
    unknown = [i for i, s in enumerate(sizes) if s == -1]
    if len(unknown) > 1:
        raise ValueError("at most one axis may be -1")
    known = int(np.prod([s for s in sizes if s != -1])) or 1
    if unknown:
        if n % known:
            raise ValueError(f"{n} devices not divisible by {known}")
        sizes[unknown[0]] = n // known
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} devices, have {n}")
    mesh_devices = np.array(devices[:total]).reshape(sizes)
    return Mesh(mesh_devices, tuple(names))


def data_parallel_mesh(devices: Optional[Sequence] = None):
    return make_mesh({"data": -1}, devices)


def mesh_axis_size(mesh, axis: str) -> int:
    return int(mesh.shape.get(axis, 1))


def multihost_mesh(axes: Dict[str, int], process_axis: str = "data"):
    """Multi-host mesh: each process contributes its local devices; the
    ``process_axis`` spans hosts (DCN), remaining axes stay intra-host (ICI).
    Call after ``jax.distributed.initialize`` (see raydp_tpu.spmd.bootstrap).

    ``jax.devices()`` orders devices process-major, so the slowest-varying
    reshape dim spans hosts: the mesh is built with ``process_axis`` outermost
    and then transposed back to the caller's axis order.
    """
    import jax
    from jax.sharding import Mesh

    if process_axis not in axes:
        raise ValueError(f"process_axis {process_axis!r} not in axes {list(axes)}")
    names = list(axes.keys())
    ordered = [process_axis] + [a for a in names if a != process_axis]
    built = make_mesh({a: axes[a] for a in ordered}, jax.devices())
    if ordered == names:
        return built
    # transpose the device array back to the caller's axis order
    perm = [ordered.index(a) for a in names]
    return Mesh(np.transpose(built.devices, perm), tuple(names))
