"""Kernel-family parity gates for the decode-native serving path
(raydp_tpu/ops/flash_attention.py; docs/serving.md "Decode serving").

Three contracts, each load-bearing for a serving guarantee:

- one-pass vs reference forward body: the deferred-rescale online-softmax
  kernel (the VPU-wall fix) must be BIT-identical to the two-branch
  reference at every shape — it is the default body, so any drift would
  silently change every flash user's numerics;
- decode-step vs prefill bit-parity at fixed batch shape: the determinism
  contract the stream-failover re-prefill rests on (a stream resumed on
  another replica continues with exactly the tokens the dead replica
  would have produced);
- int8 K/V round-trip: quantize→dequant parity within the per-row scale
  bound on K/V-shaped tensors ACROSS the kernel's block boundaries, and
  the int8 decode kernel within that bound of the f32 kernel.

All on CPU via the pallas interpreter (conftest forces JAX_PLATFORMS=cpu);
the driver's dryrun revalidates on real chips.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raydp_tpu.ops.flash_attention import (
    _flash_call,
    flash_attention,
    flash_decode,
    pick_blocks,
    use_onepass_default,
)
from raydp_tpu.ops.quantization import dequantize_int8, quantize_int8


def _qkv(b, h, t, d, seed=0, tk=None):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, tk or t, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, tk or t, d)), jnp.float32)
    return q, k, v


def test_onepass_is_default():
    assert use_onepass_default()


@pytest.mark.parametrize("shape", [(2, 3, 128, 32), (1, 2, 256, 64)])
@pytest.mark.parametrize("causal", [False, True])
def test_onepass_bit_parity(shape, causal):
    """The one-pass deferred-rescale body must match the reference body
    bit-for-bit — same shapes, same blocks, only the accumulate body
    differs. Any mismatch means the rescale restructuring changed a
    rounding somewhere, which would break every downstream exactness
    gate at once."""
    q, k, v = _qkv(*shape)
    out = {}
    for onepass in (False, True):
        o, m, l = _flash_call(  # noqa: E741
            q, k, v, 0, 0, causal, None, None, True,
            normalize=True, onepass=onepass,
        )
        out[onepass] = (np.asarray(o), np.asarray(m), np.asarray(l))
    for a, b in zip(out[False], out[True]):
        np.testing.assert_array_equal(a, b)


def _assert_decode_equals_prefill(got, ref, ulps):
    """Bit for bit on a TPU, where the property holds (chip_smoke.py's
    ``kernels`` phase asserts it there too). On the CPU backend the Pallas
    interpreter runs each block's dots through XLA:CPU, whose reduction
    order depends on the operand shapes since JAX 0.9.0: the decode's
    8-row q block and the prefill's 128-row block sum the same products
    in another order, so here the two agree to ``ulps`` float32 ulps of
    the largest reference value and no closer."""
    got, ref = np.asarray(got), np.asarray(ref)
    if jax.default_backend() == "tpu":
        np.testing.assert_array_equal(got, ref)
    else:
        bound = ulps * np.finfo(np.float32).eps * float(np.abs(ref).max())
        np.testing.assert_allclose(got, ref, rtol=0.0, atol=bound)


@pytest.mark.parametrize("kv_len", [17, 64, 128])
def test_decode_vs_prefill_kernel_bit_parity(kv_len):
    """flash_decode over a cache of ``kv_len`` valid rows must equal row
    ``kv_len - 1`` of a causal prefill at the FIXED full-cache shape
    BITWISE on a TPU (within 8 ulps on the CPU interpreter, see
    ``_assert_decode_equals_prefill``) — the shape the serving engine
    actually prefills at ([1, Tcap]), so this is the exact failover
    re-prefill contract. Per-row online-softmax math is row-independent,
    so neither the q-tiling difference (decode pads to 8 sublanes) nor the
    garbage cache rows past kv_len (masked to exact zeros) may matter."""
    b, h, tcap, d = 2, 3, 128, 32
    rng = np.random.default_rng(7)
    q_full = jnp.asarray(rng.standard_normal((b, h, tcap, d)), jnp.float32)
    k_cache = jnp.asarray(rng.standard_normal((b, h, tcap, d)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((b, h, tcap, d)), jnp.float32)

    ref = flash_attention(q_full, k_cache, v_cache, True, interpret=True)
    got = flash_decode(
        q_full[:, :, kv_len - 1: kv_len],
        k_cache, v_cache,
        jnp.full((b,), kv_len, jnp.int32),
        interpret=True,
    )
    _assert_decode_equals_prefill(got, ref[:, :, kv_len - 1: kv_len], ulps=8)


def test_decode_mixed_lengths_match_per_seq_prefill():
    """A decode batch whose sequences sit at DIFFERENT lengths (the
    continuous-batching steady state) must give each sequence the same
    rows a per-sequence prefill gives — batch composition independence
    at the fixed compiled shape."""
    b, h, tcap, d = 3, 2, 64, 16
    lengths = [9, 33, 64]
    rng = np.random.default_rng(3)
    k_cache = jnp.asarray(rng.standard_normal((b, h, tcap, d)), jnp.float32)
    v_cache = jnp.asarray(rng.standard_normal((b, h, tcap, d)), jnp.float32)
    q_last = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)

    got = flash_decode(
        q_last, k_cache, v_cache, jnp.asarray(lengths, jnp.int32),
        interpret=True,
    )
    for i, ln in enumerate(lengths):
        # per-sequence reference: causal attention of the last position
        # against its own ln valid rows (batch of 1)
        qf = jnp.concatenate(
            [jnp.zeros((1, h, ln - 1, d), jnp.float32), q_last[i:i + 1]],
            axis=2,
        )
        ref = flash_attention(
            qf, k_cache[i:i + 1, :, :ln], v_cache[i:i + 1, :, :ln], True,
            interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(got[i]), np.asarray(ref[0, :, -1:]),
            rtol=0, atol=1e-6,
        )


def test_int8_kv_roundtrip_across_block_boundaries():
    """quantize→dequant parity on K/V-shaped tensors spanning the decode
    kernel's block_k boundaries: the per-row (per position, per head)
    error must stay within scale/2 elementwise EVERYWHERE — a row
    straddling a block boundary gets no special treatment, so a bound
    violation localized to a boundary would expose a row/scale
    misalignment in the paged layout."""
    b, h, tk, d = 2, 3, 160, 32  # tk deliberately not a block multiple
    rng = np.random.default_rng(11)
    kv = rng.standard_normal((b, h, tk, d)).astype(np.float32) * 3.0
    flat = jnp.asarray(kv.reshape(b * h * tk, d))
    vals, scales = quantize_int8(flat)
    back = np.asarray(dequantize_int8(vals, scales)).reshape(b, h, tk, d)
    scale_per_row = np.asarray(scales).reshape(b, h, tk, 1)
    err = np.abs(back - kv)
    assert np.all(err <= scale_per_row / 2 + 1e-7), float(err.max())
    # and the bound is per-ROW: rows quantized independently, so the max
    # error of a row tracks that row's own scale, not the global max
    _, bq, bk = (None, *pick_blocks(8, tk, head_dim=d))
    for edge in range(bk, tk, bk):
        boundary_err = err[:, :, edge - 1: edge + 1]
        boundary_scale = scale_per_row[:, :, edge - 1: edge + 1]
        assert np.all(boundary_err <= boundary_scale / 2 + 1e-7)


def test_int8_decode_within_quantization_bound():
    """The int8 decode kernel (on-the-fly dequant) must agree with the f32
    kernel run on the dequantized cache EXACTLY — dequant-then-attend and
    attend-with-inline-dequant are the same arithmetic — and with the
    unquantized f32 kernel within the propagated quantization error."""
    b, h, tcap, d = 2, 2, 64, 32
    kv_len = 50
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.standard_normal((b, h, 1, d)), jnp.float32)
    k = rng.standard_normal((b, h, tcap, d)).astype(np.float32)
    v = rng.standard_normal((b, h, tcap, d)).astype(np.float32)
    lens = jnp.full((b,), kv_len, jnp.int32)

    def q8(x):
        vals, scales = quantize_int8(jnp.asarray(x.reshape(b * h * tcap, d)))
        return (
            jnp.asarray(vals).reshape(b, h, tcap, d),
            jnp.asarray(scales).reshape(b, h, tcap),
        )

    k8, ks = q8(k)
    v8, vs = q8(v)
    got_int8 = np.asarray(flash_decode(
        q, k8, v8, lens, k_scale=ks, v_scale=vs, interpret=True
    ))
    k_dq = np.asarray(k8, np.float32) * np.asarray(ks)[..., None]
    v_dq = np.asarray(v8, np.float32) * np.asarray(vs)[..., None]
    got_dq = np.asarray(flash_decode(
        q, jnp.asarray(k_dq), jnp.asarray(v_dq), lens, interpret=True
    ))
    np.testing.assert_array_equal(got_int8, got_dq)
    got_f32 = np.asarray(flash_decode(
        q, jnp.asarray(k), jnp.asarray(v), lens, interpret=True
    ))
    np.testing.assert_allclose(got_int8, got_f32, atol=0.05)


def _tiny_lm(tcap, plen):
    from raydp_tpu.models.transformer import TransformerLM

    vocab, d_model, heads = 61, 32, 2
    model = TransformerLM(
        vocab_size=vocab, d_model=d_model, num_heads=heads,
        num_layers=2, max_len=tcap + 1, attn_impl="flash",
        dtype=jnp.float32,
    )
    rng = np.random.default_rng(0)
    toks = rng.integers(0, vocab, (1, plen + 1), dtype=np.int32)
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(toks))
    return model, params, toks, heads, d_model // heads


def test_model_decode_vs_prefill_bit_parity():
    """TransformerLM end to end at a FIXED batch shape: logits from a
    single-token decode step against cached K/V must equal the prefill
    logits at that position bitwise on a TPU (f32 model, flash attention;
    within 32 ulps of the largest logit on the CPU interpreter, two layers
    of the kernels' 8) — the whole-model statement of the kernel parity,
    and the exact property the chaos re-prefill gate asserts through the
    serving stack."""
    tcap, plen = 32, 7
    model, params, toks, heads, head_dim = _tiny_lm(tcap, plen)

    # prefill over plen+1 tokens: reference logits at the last position
    ref_logits, kv = model.apply(
        params, jnp.asarray(toks), return_kv=True
    )

    # decode: cache holds the first plen tokens' K/V, step on token plen
    caches = []
    for k_h, v_h in kv:
        k_cache = jnp.zeros((1, heads, tcap, head_dim), jnp.float32)
        v_cache = jnp.zeros((1, heads, tcap, head_dim), jnp.float32)
        k_cache = k_cache.at[:, :, :plen].set(k_h[:, :, :plen])
        v_cache = v_cache.at[:, :, :plen].set(v_h[:, :, :plen])
        caches.append((k_cache, v_cache))
    step_logits, _ = model.apply(
        params,
        jnp.asarray(toks[:, plen:plen + 1]),
        kv_caches=caches,
        kv_len=jnp.asarray([plen + 1], jnp.int32),
    )
    _assert_decode_equals_prefill(
        step_logits[0, -1], ref_logits[0, plen], ulps=32
    )


def test_model_greedy_tokens_decode_equals_prefill():
    """What a client sees of the parity on any backend: greedy tokens from
    single-token decode steps against the growing cache equal the tokens a
    fresh prefill of the whole sequence picks, step after step (the logits
    part by a few ulps on the CPU interpreter; the argmax does not)."""
    tcap, plen, steps = 32, 7, 4
    model, params, toks, heads, head_dim = _tiny_lm(tcap, plen)

    def greedy(logits):
        return int(np.argmax(np.asarray(logits[0, -1])))

    logits, kv = model.apply(
        params, jnp.asarray(toks[:, :plen]), return_kv=True
    )
    caches = [
        (
            jnp.zeros((1, heads, tcap, head_dim), jnp.float32)
            .at[:, :, :plen].set(k_h),
            jnp.zeros((1, heads, tcap, head_dim), jnp.float32)
            .at[:, :, :plen].set(v_h),
        )
        for k_h, v_h in kv
    ]
    first = greedy(logits)
    decoded, last, n = [], first, plen
    for _ in range(steps):
        step_logits, new = model.apply(
            params, jnp.asarray([[last]], jnp.int32), kv_caches=caches,
            kv_len=jnp.asarray([n + 1], jnp.int32),
        )
        caches = [
            (kc.at[:, :, n:n + 1].set(nk), vc.at[:, :, n:n + 1].set(nv))
            for (kc, vc), (nk, nv) in zip(caches, new)
        ]
        n += 1
        last = greedy(step_logits)
        decoded.append(last)

    seq = np.concatenate([toks[:, :plen], [[first]]], 1).astype(np.int32)
    prefilled = []
    for _ in range(steps):
        prefilled.append(greedy(model.apply(params, jnp.asarray(seq))))
        seq = np.concatenate([seq, [[prefilled[-1]]]], 1).astype(np.int32)
    assert decoded == prefilled
