"""Expert parallelism: top-k mixture-of-experts with all-to-all dispatch.

Each device on the ``ep`` axis hosts ONE expert. Tokens are data-sharded over
the same axis; a replicated router assigns each token its top-k experts
(renormalized gates); dispatch builds per-expert capacity buffers, an
all-to-all ships every device's buffer for expert e to device e, the expert
runs on its combined buffer, and the inverse all-to-all + weighted combine
returns outputs to the tokens' home devices. Capacity is allocated
first-choice-first (GShard priority): second choices are the first dropped
when an expert overflows, and dropped (token, choice) pairs contribute 0.

Router health is a first-class output (``return_aux=True``):
- ``load_balance_loss`` — the Switch-Transformer auxiliary loss
  N * Σ_n f_n · P_n (f_n = routed fraction to expert n PRE-capacity, P_n =
  mean router probability); 1.0 at perfect balance, grows as the router
  collapses. Add
  ``aux_weight * load_balance_loss`` to the task loss to train against
  collapse.
- ``drop_fraction`` — fraction of (token, choice) pairs dropped by capacity;
  silent in round 1, now observable.

All dispatch/combine math is one-hot einsums: MXU-friendly, fully
differentiable (gradients flow through the gate weights), no gathers.
The reference has no MoE at all (SURVEY.md §2.4: TP/PP/SP/EP absent).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax



def moe_apply(
    expert_fn: Callable,
    expert_params,
    router_weights: jnp.ndarray,  # [D, N] replicated
    x: jnp.ndarray,  # [B_local, D] this device's token shard
    axis_name: str = "ep",
    capacity_factor: float = 1.25,
    top_k: int = 1,
    return_aux: bool = False,
):
    """Call inside shard_map. ``expert_params`` is THIS device's expert.

    Returns the combined output [B_local, D_out]; with ``return_aux=True``
    returns ``(out, {"load_balance_loss", "drop_fraction"})`` where the aux
    scalars are pmean'd over ``axis_name`` (identical on every device).
    """
    import math

    n = lax.axis_size(axis_name)
    b, d = x.shape
    k = min(top_k, n)
    # ceil keeps the requested headroom even at small per-device batches;
    # scales with k because every token now occupies up to k slots
    capacity = max(1, math.ceil(b * k * capacity_factor / n))

    logits = x @ router_weights  # [B, N]
    gates = jax.nn.softmax(logits, axis=-1)
    top_vals, top_idx = lax.top_k(gates, k)  # [B, K]
    if k > 1:
        # renormalize the chosen gates (GShard): combine weights sum to 1
        weights = top_vals / jnp.maximum(
            jnp.sum(top_vals, axis=-1, keepdims=True), 1e-30
        )
    else:
        weights = top_vals

    # slot bookkeeping in f32 regardless of x.dtype: a bf16 cumsum saturates
    # at 256 and silently collides capacity slots. Choice-major flattening
    # gives first choices strictly higher capacity priority than second.
    oh = jax.nn.one_hot(top_idx.T, n, dtype=jnp.float32)  # [K, B, N]
    pos = (jnp.cumsum(oh.reshape(k * b, n), axis=0) - 1.0).reshape(k, b, n) * oh
    in_capacity = pos < capacity
    dispatch_mask = oh * in_capacity  # [K, B, N]
    slot_one_hot = jax.nn.one_hot(
        pos.astype(jnp.int32), capacity, dtype=jnp.float32
    )  # [K, B, N, C]
    dispatch_k = slot_one_hot * dispatch_mask[..., None]  # [K, B, N, C]
    # send each token once per chosen expert; fold the gate weight into the
    # combine side only
    dispatch_send = jnp.sum(dispatch_k, axis=0).astype(x.dtype)  # [B, N, C]
    combine_w = jnp.einsum(
        "kbnc,bk->bnc", dispatch_k, weights.astype(jnp.float32)
    ).astype(x.dtype)

    # local per-expert buffers [N, C, D] → ship buffer e to device e; the
    # tiled all_to_all splits the expert dim across devices and concatenates
    # the received chunks along the slot dim: result [1, C*n, D] — all
    # devices' capacity buffers for MY expert
    buffers = jnp.einsum("bnc,bd->ncd", dispatch_send, x)
    received = lax.all_to_all(
        buffers, axis_name, split_axis=0, concat_axis=1, tiled=True
    )
    received = received.reshape(n * capacity, d)

    expert_out = expert_fn(expert_params, received)  # [n*C, D_out]
    d_out = expert_out.shape[-1]
    expert_out = expert_out.reshape(1, n * capacity, d_out)

    # inverse: send each source device its slice back
    returned = lax.all_to_all(
        expert_out, axis_name, split_axis=1, concat_axis=0, tiled=True
    )  # [n, C, D_out] — my tokens' outputs, per assigned expert
    out = jnp.einsum("bnc,ncd->bd", combine_w, returned)
    if not return_aux:
        return out

    # Switch-Transformer load-balancing loss: N * Σ_n f_n · P_n. f_n is the
    # ROUTED fraction (pre-capacity, standard Switch formulation — it can
    # exceed what was actually dispatched when drops occur) and is constant
    # wrt the router — gradients flow through P_n, pushing probability mass
    # toward under-used experts.
    f = jnp.mean(oh, axis=(0, 1))  # [N] fraction of choices per expert
    p = jnp.mean(gates, axis=0)  # [N] mean router probability
    aux = {
        "load_balance_loss": lax.pmean(
            n * jnp.sum(lax.stop_gradient(f) * p), axis_name
        ),
        "drop_fraction": lax.pmean(
            1.0 - jnp.sum(dispatch_mask) / (b * k), axis_name
        ),
    }
    return out, aux


def moe_sharded(
    expert_fn: Callable,
    stacked_expert_params,
    router_weights: jnp.ndarray,
    x: jnp.ndarray,  # [B, D] global
    mesh,
    axis: str = "ep",
    capacity_factor: float = 1.25,
    top_k: int = 1,
    return_aux: bool = False,
):
    """Global wrapper: expert params stacked on a leading dim sharded over
    ``axis``; tokens sharded over the same axis (dp=ep co-located)."""
    from jax.sharding import PartitionSpec as P

    def body(params_local, router, x_local):
        params = jax.tree.map(lambda p: p[0], params_local)
        return moe_apply(
            expert_fn, params, router, x_local,
            axis_name=axis, capacity_factor=capacity_factor,
            top_k=top_k, return_aux=return_aux,
        )

    out_specs = P(axis)
    if return_aux:
        out_specs = (
            P(axis),
            {"load_balance_loss": P(), "drop_fraction": P()},
        )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(axis), stacked_expert_params), P(), P(axis)),
        out_specs=out_specs,
    )(stacked_expert_params, router_weights, x)
