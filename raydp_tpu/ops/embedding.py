"""Embedding lookup over vocab-sharded tables.

BASELINE.md's DLRM config asks for sharded embedding tables (the reference
trains DLRM pure-DP with replicated tables — its only model-parallel-adjacent
need). Two idiomatic TPU paths:

- **GSPMD (default)**: shard the table with ``NamedSharding(P("model", None))``
  and just ``jnp.take`` — XLA partitions the gather and inserts the collective.
  This is what models/dlrm.py uses via param_sharding_rules.
- **Explicit (this module)**: a shard_map mask-gather-psum, for when you want
  the collective schedule pinned down rather than left to the partitioner
  (e.g. to overlap with other compute, or under a ``shard_map``-only step).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax import lax


def embedding_lookup_vocab_sharded(
    table: jnp.ndarray, ids: jnp.ndarray, axis_name: str
) -> jnp.ndarray:
    """Per-device body (call inside shard_map): ``table`` is the local vocab
    shard [V/N, D]; ``ids`` are global ids (replicated). Each device gathers
    the ids that fall in its shard and a psum assembles full rows."""
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    local_v = table.shape[0]
    start = my * local_v
    local_ids = ids - start
    in_range = (local_ids >= 0) & (local_ids < local_v)
    safe_ids = jnp.clip(local_ids, 0, local_v - 1)
    rows = jnp.take(table, safe_ids, axis=0)
    rows = jnp.where(in_range[..., None], rows, 0.0)
    return lax.psum(rows, axis_name)


def sharded_embedding_lookup(
    table: jnp.ndarray, ids: jnp.ndarray, mesh, axis: str = "model"
) -> jnp.ndarray:
    """Global-array convenience wrapper: table sharded [V, D] over ``axis``,
    ids replicated; returns replicated rows."""
    from jax.sharding import PartitionSpec as P

    return jax.shard_map(
        partial(embedding_lookup_vocab_sharded, axis_name=axis),
        mesh=mesh,
        in_specs=(P(axis, None), P()),
        out_specs=P(),
    )(table, ids)
