"""Quantization kernels: int8 with per-row scales, stochastic rounding.

Host→device bandwidth and HBM footprint both shrink 4× when exchange blocks
or activations travel as int8 + f32 scales. On TPU the stochastic path is a
row-tiled pallas kernel (per-core PRNG, mantissa bit-trick uniform); off-TPU
the same math runs via jax.random (the TPU PRNG primitives have no CPU
lowering, interpreted or otherwise — the kernel itself is validated on real
hardware). Deterministic rounding is a plain jnp path, exactly invertible to
within one quantum.

Stochastic rounding is unbiased only if the seed varies per call — derive it
from a step counter; reusing one seed correlates the rounding error across
steps and accumulates bias on slowly-changing tensors.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from raydp_tpu.ops.backend import on_tpu


def quantize_int8(x: jnp.ndarray, seed: int | None = None, stochastic: bool = False,
                  block_rows: int = 256):
    """[N, D] f32 → (int8 values [N, D], f32 scales [N, 1]); row-wise scales.
    ``seed`` is required when ``stochastic=True`` (vary it per step)."""
    if not stochastic:
        scales = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0
        scales = jnp.maximum(scales, 1e-12)
        values = jnp.clip(jnp.round(x / scales), -127, 127).astype(jnp.int8)
        return values, scales
    if seed is None:
        raise ValueError("stochastic quantization requires a per-step seed")
    if not on_tpu():
        scales = jnp.maximum(
            jnp.max(jnp.abs(x), axis=-1, keepdims=True) / 127.0, 1e-12
        )
        uniform = jax.random.uniform(jax.random.PRNGKey(seed), x.shape)
        values = jnp.clip(jnp.floor(x / scales + uniform), -127, 127).astype(jnp.int8)
        return values, scales
    return _quantize_pallas(x, seed, block_rows)


def dequantize_int8(values: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    return values.astype(jnp.float32) * scales


@jax.custom_vjp
def int8_matmul(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``x [..., K] @ w [K, M]`` computed on the MXU's int8 path (2x the
    bf16 rate on v5e/v5p): activations quantize per-row, weights per-column,
    the dot runs int8xint8->int32, and the output dequantizes by the outer
    product of scales. Training-safe via the straight-through estimator —
    the backward pass differentiates the EXACT matmul at the float inputs
    (standard int8-forward training recipe), so gradients are the bf16
    matmul gradients, not zero (quantize's round has no gradient).

    Quantization error is bounded by the per-row/column max-abs scaling
    (~0.4% relative per operand); intended for the MLP blocks where the
    4d contraction amortizes the quantize/dequantize VPU work."""
    xq, xs = quantize_int8(x.reshape(-1, x.shape[-1]).astype(jnp.float32))
    wq, ws = quantize_int8(w.T.astype(jnp.float32))  # per-COLUMN scales of w
    y = jax.lax.dot_general(
        xq, wq.T,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out = y.astype(jnp.float32) * xs * ws.T
    return out.reshape(x.shape[:-1] + (w.shape[-1],))


def _int8_matmul_fwd(x, w):
    return int8_matmul(x, w), (x, w)


def _int8_matmul_bwd(res, g):
    x, w = res
    # straight-through: grads of the exact float matmul, in the inputs'
    # dtypes (bf16 keeps the backward on the MXU's bf16 path)
    gx = jnp.einsum("...m,km->...k", g.astype(x.dtype), w.astype(x.dtype))
    gw = jnp.einsum(
        "...k,...m->km",
        x.astype(jnp.float32),
        g.astype(jnp.float32),
    ).astype(w.dtype)
    return gx.astype(x.dtype), gw


int8_matmul.defvjp(_int8_matmul_fwd, _int8_matmul_bwd)


def int8_dot_general(
    lhs, rhs, dimension_numbers, precision=None, preferred_element_type=None
):
    """Drop-in ``dot_general`` for ``flax.linen.Dense(dot_general=...)``:
    routes the Dense contraction ([..., K] x [K, M]) through int8_matmul
    (output cast back to the promoted input dtype so downstream activations
    keep the module's dtype); any other contraction falls through to lax.
    Using it keeps the param tree IDENTICAL to a plain Dense, so bf16 and
    int8-forward checkpoints interchange freely."""
    ((lc, rc), (lb, rb)) = dimension_numbers
    if tuple(lc) == (lhs.ndim - 1,) and tuple(rc) == (0,) and not lb and not rb:
        return int8_matmul(lhs, rhs).astype(lhs.dtype)
    return jax.lax.dot_general(
        lhs, rhs, dimension_numbers,
        precision=precision, preferred_element_type=preferred_element_type,
    )


def _quant_kernel(x_ref, seed_ref, values_ref, scales_ref):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # fold the row-block index into the seed so tiles draw independent noise
    pltpu.prng_seed(seed_ref[0] + pl.program_id(0))
    x = x_ref[:]
    abs_max = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(abs_max / 127.0, 1e-12)
    scaled = x / scale
    # uniform [0,1) via the mantissa bit-trick (Mosaic lacks uint32→f32 cast):
    # top 23 random bits + exponent of 1.0 bitcast to f32 ∈ [1,2), minus 1
    random_bits = pltpu.bitcast(pltpu.prng_random_bits(scaled.shape), jnp.uint32)
    mantissa = (random_bits >> 9) | jnp.uint32(0x3F800000)
    uniform = pltpu.bitcast(mantissa, jnp.float32) - 1.0
    rounded = jnp.floor(scaled + uniform)
    values_ref[:] = jnp.clip(rounded, -127, 127).astype(jnp.int8)
    scales_ref[:] = jnp.broadcast_to(scale, scales_ref.shape)


def _quantize_pallas(x: jnp.ndarray, seed: int, block_rows: int):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, d = x.shape
    block_rows = min(block_rows, n)
    if n % block_rows:  # pad rows so the grid divides evenly
        pad = block_rows - n % block_rows
        x = jnp.concatenate([x, jnp.zeros((pad, d), x.dtype)], axis=0)
    padded_n = x.shape[0]
    grid = (padded_n // block_rows,)
    values, scales = pl.pallas_call(
        _quant_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((padded_n, d), jnp.int8),
            jax.ShapeDtypeStruct((padded_n, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=(
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ),
    )(x, jnp.asarray([seed], jnp.int32))
    return values[:n], scales[:n]
