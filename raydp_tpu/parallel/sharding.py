"""Parameter-sharding rules: regex-on-path → PartitionSpec.

The one genuinely model-parallel artifact the reference's workloads need is
DLRM's sharded embedding tables (BASELINE.md); here that is a rule like
``(r"embedding", P("model", None))``. Everything else defaults to replicated.
"""

from __future__ import annotations

import re
from typing import Any, Callable, List, Sequence, Tuple


def shard_params_by_rules(
    mesh,
    params,
    rules: Sequence[Tuple[str, Any]],
    default=None,
):
    """pytree of NamedShardings: first rule whose regex matches the param's
    '/'-joined path wins; unmatched params use ``default`` (replicated).

    Shapes that don't divide the mesh axis fall back to replication rather
    than failing inside jit with an opaque sharding error.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, default or PartitionSpec())
    compiled = [(re.compile(pattern), spec) for pattern, spec in rules]

    def resolve(path, leaf):
        path_str = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        for regex, spec in compiled:
            if regex.search(path_str):
                if _divisible(leaf.shape, spec, mesh):
                    return NamedSharding(mesh, spec)
                return replicated
        return replicated

    return jax.tree_util.tree_map_with_path(resolve, params)


def _divisible(shape, spec, mesh) -> bool:
    for dim, axis in zip(shape, tuple(spec)):
        if axis is None:
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        size = 1
        for a in axes:
            size *= int(mesh.shape.get(a, 1))
        if dim % size:
            return False
    return True


def sharding_rules_fn(rules: Sequence[Tuple[str, Any]]) -> Callable:
    """Adapter for JaxEstimator(param_sharding_rules=...)."""

    def fn(mesh, params):
        return shard_params_by_rules(mesh, params, rules)

    return fn
