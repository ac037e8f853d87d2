"""Causal transformer LM with pluggable long-context attention.

Nothing like this exists in the reference (no sequence models at all); it is
here because long-context is first-class in this framework: the same block
runs single-device full attention, ring attention (sequence ring-sharded over
an ``sp`` mesh axis, raydp_tpu.parallel.ring_attention), or Ulysses
all-to-all head parallelism — selected by config, identical math.

bfloat16 by default: attention/matmul FLOPs target the MXU.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from raydp_tpu.parallel.ring_attention import (
    full_attention,
    ring_attention,
    ulysses_attention,
)


def _attend(q, k, v, *, impl: str, axis: str, causal: bool,
            window: int | None = None):
    """``window`` (a query sees its own position and the ``window - 1``
    before it) is built by ``full`` and ``flash`` only."""
    if impl == "skip":
        # diagnostic: attention replaced by identity — isolates the
        # non-attention step time for roofline decomposition (bench only)
        return v
    if impl == "full":
        return full_attention(q, k, v, causal=causal, window=window)
    if impl == "flash":
        from raydp_tpu.ops.flash_attention import flash_attention

        # default blocks = pick_blocks: the measured-fastest large tiles
        return flash_attention(q, k, v, causal, window=window)
    if window is not None:
        raise ValueError(f"attention impl {impl!r} builds no window")
    if impl == "ring":
        return ring_attention(q, k, v, axis_name=axis, causal=causal)
    if impl == "ring_flash":
        # ring schedule with the fused pallas flash kernel computing each
        # (Q-block, K/V-block) product — the long-context production path:
        # O(T_local) memory from the ring AND VMEM-blocked exact attention
        # per step
        return ring_attention(
            q, k, v, axis_name=axis, causal=causal, use_flash=True
        )
    if impl == "ulysses":
        return ulysses_attention(q, k, v, axis_name=axis, causal=causal)
    if impl == "ulysses_flash":
        # all-to-all head parallelism with the fused flash kernel on the
        # gathered local sequence
        return ulysses_attention(
            q, k, v, axis_name=axis, causal=causal, use_flash=True
        )
    raise ValueError(f"unknown attention impl {impl!r}")


def attention_backward_facts(impl: str, t: int, head_dim: int, dtype,
                             layers: dict, value_dim: int = None,
                             window: int = None) -> dict:
    """What a model whose attention goes through ``_attend`` says in its
    ``fit_facts`` of the attention's kernels over rows of ``t`` tokens.
    ``layers``: {layer kind (``global``, ``window``, ``latent``): its
    layer applications a step}; ``value_dim``: v's and o's width where it is
    not ``head_dim``; ``window``: the ``window`` kind's.
    ``attention_backward``: the form a kind's backward
    pass takes, from the shapes (``ops.flash_attention.backward_form``:
    ``fused``, one call that computes every live tile once, or
    ``two_call``; a ring's step has runtime offsets and is ``two_call``;
    ``xla`` where no flash kernel runs). ``attention.backward_fused_layers``:
    the layer applications a step whose backward is the fused call.
    ``attention.dq_resident_bytes``: what that call keeps in VMEM for a
    head's float32 dq. ``attention_grid``: the grid a kind's calls, forward
    and backward, step over (``ops.flash_attention.causal_grid``: ``live``,
    the tiles under the diagonal alone; ``window``, bounded by the window;
    ``rectangular`` and why: a ring's runtime offsets). Where a causal flash
    call runs, ``attention.causal_grid_live_share``: the tiles with an
    unmasked entry over the steps those calls' grids take a head, in % (100
    on the live grid; 53.1 on a 16 x 16 rectangle: a ring's calls counted
    as one device's over the whole row)."""
    from raydp_tpu.ops.flash_attention import (
        backward_form, causal_grid, causal_steps, dq_resident_bytes,
        pick_blocks)

    form, grids, itemsize = "xla", {}, jnp.dtype(dtype).itemsize
    if impl in ("flash", "ulysses_flash", "ring_flash"):
        ring = impl == "ring_flash"
        form = "two_call" if ring else backward_form(
            t, t, head_dim, itemsize, value_dim=value_dim)
        blocks = pick_blocks(t, t, head_dim, itemsize, value_dim)
        # a ring step's offsets are traced values: any but the static 0
        grids = {kind: causal_grid(
            t, t, *blocks, window=window if kind == "window" else None,
            q_offset=None if ring else 0) for kind in layers}
    fused = sum(layers.values()) if form == "fused" else 0
    facts = {
        "attention_backward": ",".join(f"{kind}={form}" for kind in layers),
        "attention.backward_fused_layers": fused,
        "attention.dq_resident_bytes":
            dq_resident_bytes(t, head_dim) if fused else 0,
        "attention_grid": ",".join(
            f"{kind}={grids.get(kind, 'xla')}" for kind in layers)}
    causal = {grid for grid in grids.values() if grid != "window"}
    if causal:
        steps, live_tiles = causal_steps(t, *blocks)
        facts["attention.causal_grid_live_share"] = 100.0 * live_tiles / (
            live_tiles if causal == {"live"} else steps)
    return facts


def _scatter_rows(cache, new, starts):
    """Insert ``new`` [B, H, t, D] into ``cache`` [B, H, T, D] at per-batch
    position ``starts`` [B] along the sequence dim (vmapped dynamic update —
    each sequence in a decode batch sits at its own length)."""
    def one(c, n, s):
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (0, s, 0))

    return jax.vmap(one)(cache, new, starts)


def _scatter_scales(cache, new, starts):
    """Same as ``_scatter_rows`` for [B, H, T] per-row scale planes."""
    def one(c, n, s):
        return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (0, s))

    return jax.vmap(one)(cache, new, starts)


def _decode_attend(q, k_new, v_new, decode_kv, kv_len):
    """Incremental-decode attention: the new rows' K/V join the cached
    sequence in-graph (per-batch scatter at each sequence's length), then
    ``ops.flash_decode`` attends the last ``t`` positions against the whole
    cache with per-sequence valid-length masking. ``decode_kv`` is either
    (k, v) dense f32 caches [B, H, Tcap, D] — the bit-exact mode the
    decode-vs-prefill determinism contract is stated for — or
    (k_int8, k_scale, v_int8, v_scale) with on-the-fly dequant in-kernel."""
    from raydp_tpu.ops.flash_attention import flash_decode

    t = q.shape[2]
    starts = kv_len - t
    if len(decode_kv) == 2:
        k_cache, v_cache = decode_kv
        k_full = _scatter_rows(k_cache, k_new, starts)
        v_full = _scatter_rows(v_cache, v_new, starts)
        return flash_decode(q, k_full, v_full, kv_len)

    from raydp_tpu.ops.quantization import quantize_int8

    k8, k_sc, v8, v_sc = decode_kv
    b, h, tn, d = k_new.shape

    def quant(x):
        vals, scales = quantize_int8(x.astype(jnp.float32).reshape(b * h * tn, d))
        return vals.reshape(b, h, tn, d), scales.reshape(b, h, tn)

    kq, kqs = quant(k_new)
    vq, vqs = quant(v_new)
    return flash_decode(
        q,
        _scatter_rows(k8, kq, starts),
        _scatter_rows(v8, vq, starts),
        kv_len,
        k_scale=_scatter_scales(k_sc, kqs, starts),
        v_scale=_scatter_scales(v_sc, vqs, starts),
    )


class Block(nn.Module):
    num_heads: int
    attn_impl: str = "full"
    seq_axis: str = "sp"
    dtype: jnp.dtype = jnp.bfloat16
    # forward MLP matmuls on the MXU's int8 path (2x the bf16 rate on
    # v5e/v5p; ops/quantization.int8_matmul — straight-through gradients,
    # backward stays bf16). Opt-in: ~0.4% relative quantization error per
    # matmul on the forward activations.
    quantized_mlp: bool = False

    @nn.compact
    def __call__(self, x, *, decode_kv=None, kv_len=None, return_kv=False):
        d_model = x.shape[-1]
        head_dim = d_model // self.num_heads
        y = nn.LayerNorm(dtype=self.dtype)(x)
        qkv = nn.Dense(3 * d_model, dtype=self.dtype, name="qkv")(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)

        def heads(z):  # [B, T, D] -> [B, H, T, Dh]
            b, t, _ = z.shape
            return z.reshape(b, t, self.num_heads, head_dim).transpose(0, 2, 1, 3)

        q_h, k_h, v_h = heads(q), heads(k), heads(v)
        if decode_kv is not None:
            o = _decode_attend(q_h, k_h, v_h, decode_kv, kv_len)
        else:
            o = _attend(
                q_h, k_h, v_h,
                impl=self.attn_impl, axis=self.seq_axis, causal=True,
            )
        b, h, t, hd = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(b, t, h * hd)
        x = x + nn.Dense(d_model, dtype=self.dtype, name="proj")(o)

        y = nn.LayerNorm(dtype=self.dtype)(x)
        mlp_kw = {}
        if self.quantized_mlp:
            from raydp_tpu.ops.quantization import int8_dot_general

            # same nn.Dense modules, custom contraction: the param tree is
            # identical to the bf16 path, so checkpoints interchange freely
            mlp_kw["dot_general"] = int8_dot_general
        y = nn.Dense(4 * d_model, dtype=self.dtype, **mlp_kw)(y)
        y = nn.gelu(y)
        y = nn.Dense(d_model, dtype=self.dtype, **mlp_kw)(y)
        out = x + y
        if decode_kv is not None or return_kv:
            # the new rows' K/V in head layout — the decode engine appends
            # them to its paged cache after the step
            return out, (k_h, v_h)
        return out


class TransformerLM(nn.Module):
    vocab_size: int
    d_model: int = 256
    num_heads: int = 8
    num_layers: int = 4
    max_len: int = 8192
    attn_impl: str = "full"  # "full" | "flash" | "ring" | "ring_flash" | "ulysses"
    seq_axis: str = "sp"
    dtype: jnp.dtype = jnp.bfloat16
    remat: bool = False
    quantized_mlp: bool = False  # int8-MXU forward MLP matmuls (see Block)

    @nn.compact
    def __call__(
        self, tokens, seq_offset=0, *, kv_caches=None, kv_len=None,
        return_kv=False,
    ):  # tokens [B, T_local] int32
        """``seq_offset`` is this shard's global position offset (0 when the
        full sequence is local; axis_index * T_local under shard_map).

        Incremental decode (``kv_caches``/``kv_len``): ``tokens`` holds each
        sequence's newest ``t`` tokens, ``kv_len`` [B] int32 their total
        lengths INCLUDING those tokens, and ``kv_caches`` one per-layer dense
        cache tuple (see ``_decode_attend``). Positions come from ``kv_len``
        per sequence, overriding ``seq_offset``. Returns (logits, new_kv)
        where ``new_kv`` is a per-layer list of the new rows' (k, v) in
        [B, H, t, Dh] layout for the caller's paged cache. ``return_kv``
        gives the same (logits, new_kv) from a prefill pass — the cache-warm
        path."""
        decode = kv_caches is not None
        x = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype)(tokens)
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (self.max_len, self.d_model),
            jnp.float32,
        )
        t = tokens.shape[1]
        if decode:
            starts = jnp.asarray(kv_len, jnp.int32) - t
            pos_slice = jax.vmap(
                lambda s: jax.lax.dynamic_slice_in_dim(pos, s, t, axis=0)
            )(starts)  # [B, t, d_model]
        else:
            pos_slice = jax.lax.dynamic_slice_in_dim(pos, seq_offset, t, axis=0)
        x = x + pos_slice.astype(self.dtype)
        block_cls = Block
        if self.remat:
            block_cls = nn.remat(Block)
        new_kv = []
        for layer in range(self.num_layers):
            block = block_cls(
                num_heads=self.num_heads,
                attn_impl=self.attn_impl,
                seq_axis=self.seq_axis,
                dtype=self.dtype,
                quantized_mlp=self.quantized_mlp,
            )
            if decode:
                x, kv = block(x, decode_kv=kv_caches[layer], kv_len=kv_len)
                new_kv.append(kv)
            elif return_kv:
                x, kv = block(x, return_kv=True)
                new_kv.append(kv)
            else:
                x = block(x)
        x = nn.LayerNorm(dtype=self.dtype)(x)
        logits = nn.Dense(self.vocab_size, dtype=jnp.float32, name="lm_head")(x)
        if decode or return_kv:
            return logits, new_kv
        return logits


def sequence_parallel_apply(model: TransformerLM, params, tokens, mesh):
    """Apply a ring/ulysses TransformerLM with the sequence sharded over the
    model's ``seq_axis``: params replicated, tokens [B, T] split on dim 1,
    logits returned with the same sequence sharding."""
    from jax import lax
    from jax.sharding import PartitionSpec as P

    axis = model.seq_axis

    def body(p, tok):
        offset = lax.axis_index(axis) * tok.shape[1]
        return model.apply(p, tok, seq_offset=offset)

    # *_flash: the pallas interpreter can't reconcile invariant grid
    # slices with varying operands; numerics are test-validated against full
    # attention
    check_vma = model.attn_impl not in ("ring_flash", "ulysses_flash")
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(), P(None, axis)),
        out_specs=P(None, axis, None),
        check_vma=check_vma,
    )(params, tokens)
