"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pp`` axis.

Each device on the axis holds ONE stage's parameters (stacked and sharded on
the leading dim). Activations flow rightward via ``lax.ppermute`` inside a
``lax.scan`` over M + N - 1 ticks: device d computes stage d at tick t for
microbatch t - d; the first N-1 and last N-1 ticks are the pipeline bubble.
All devices execute the same program every tick (SPMD — control flow is
uniform, data is masked), so XLA compiles one step and the permutes ride ICI.

The reference has no model parallelism of any kind (SURVEY.md §2.4); this is
part of making the mesh axes (dp/tp/sp/pp/ep) first-class.
"""

from __future__ import annotations


from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax



def pipeline_apply(
    stage_fn: Callable,
    stage_params: Any,
    microbatches: jnp.ndarray,
    axis_name: str = "pp",
) -> jnp.ndarray:
    """Run a pipeline of ``N = axis_size`` stages over M microbatches.

    Call inside shard_map. Per-device arguments:
      - ``stage_params``: THIS device's stage parameters (pytree).
      - ``microbatches``: [M, B, F] — the full microbatch stream (replicated;
        only device 0 consumes it as input).
    Returns [M, B, F_out] (meaningful on the last device; replicate or
    psum-select outside as needed — see ``pipeline_sharded`` below).
    """
    n = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)
    m = microbatches.shape[0]
    ticks = m + n - 1

    right = [(i, (i + 1) % n) for i in range(n)]
    sample_out = jax.eval_shape(stage_fn, stage_params, microbatches[0])
    out_buffer = jnp.zeros((m,) + tuple(sample_out.shape), sample_out.dtype)

    def tick(carry, t):
        incoming, outputs = carry
        # stage 0 reads microbatch t (clamped; masked when t >= m)
        feed_idx = jnp.clip(t, 0, m - 1)
        first_in = lax.dynamic_index_in_dim(microbatches, feed_idx, 0, False)
        x = jnp.where(my == 0, first_in, incoming)
        y = stage_fn(stage_params, x)
        # last device banks microbatch (t - (n-1)) at ticks >= n-1
        out_idx = jnp.clip(t - (n - 1), 0, m - 1)
        should_store = (my == n - 1) & (t >= n - 1)
        updated = lax.dynamic_update_index_in_dim(
            outputs,
            jnp.where(should_store, y, lax.dynamic_index_in_dim(outputs, out_idx, 0, False)),
            out_idx,
            0,
        )
        # activations move one stage rightward
        nxt = lax.ppermute(y, axis_name, right)
        return (nxt, updated), None

    # stage inputs/outputs must be shape-uniform across stages for the permute
    if tuple(sample_out.shape) != tuple(microbatches.shape[1:]):
        raise ValueError(
            "pipeline stages must preserve activation shape "
            f"(got {microbatches.shape[1:]} -> {sample_out.shape})"
        )
    zero_in = jnp.zeros(sample_out.shape, sample_out.dtype)
    # fresh zeros are device-invariant; the carry becomes varying over the
    # pipeline axis (axis_index-dependent), so mark the initial values too
    zero_in, out_buffer = (
        lax.pcast(v, (axis_name,), to="varying") for v in (zero_in, out_buffer)
    )
    (_, outputs), _ = lax.scan(tick, (zero_in, out_buffer), jnp.arange(ticks))
    return outputs


def pipeline_sharded(
    stage_fn: Callable,
    stacked_params: Any,
    x: jnp.ndarray,
    mesh,
    num_microbatches: int,
    axis: str = "pp",
) -> jnp.ndarray:
    """Global-array wrapper: ``stacked_params`` leaves have a leading stage
    dim sharded over ``axis``; ``x`` [B, F] is split into microbatches; output
    is the pipelined result [B, F]."""
    from jax.sharding import PartitionSpec as P

    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by {num_microbatches} microbatches")
    micro = x.reshape(num_microbatches, b // num_microbatches, *x.shape[1:])

    def body(params_local, micro_all):
        # params_local leaves: [1, ...] — this device's stage; drop stage dim
        params = jax.tree.map(lambda p: p[0], params_local)
        outs = pipeline_apply(stage_fn, params, micro_all, axis_name=axis)
        # broadcast the last stage's banked outputs to every device so the
        # out_spec can be replicated
        n = lax.axis_size(axis)
        mask = (lax.axis_index(axis) == n - 1).astype(outs.dtype)
        return lax.psum(outs * mask, axis)

    param_specs = jax.tree.map(lambda _: P(axis), stacked_params)
    out = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, P()),
        out_specs=P(),
    )(stacked_params, micro)
    return out.reshape(b, *out.shape[2:])
