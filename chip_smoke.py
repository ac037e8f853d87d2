#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that raydp-tpu still starts on the chip.

    python3 chip_smoke.py                  # on a machine with a TPU
    python3 chip_smoke.py --rehearse-on-cpu   # tiny sizes, interpreted kernels

Drives the system's main path once — raw tables → trained → served — through
the entry points a user calls, at the full width of the models the repo
supports (depth and row counts cut, weights random from a seed), and checks
what comes out by the repo's own means. It claims nothing about speed.

A chip belongs to one process, so THIS process never imports JAX. It runs
four phases, each in its own child, one after another, and stops at the first
that fails:

``kernels``  every Pallas kernel in ``raydp_tpu/ops`` compiled (never
             interpreted, on a TPU) at the shapes the other phases use,
             against its ``jax.numpy`` reference; decode-vs-prefill and
             one-pass-vs-two-pass bit parity measured; ``block_until_ready``
             timed against a value fetch on one multi-second computation.
``fit``      ``init_etl(num_executors=2)`` → Criteo-shaped frame →
             ``F.log1p``/``F.hash`` → ``random_split`` →
             ``JaxEstimator(DLRM).fit_on_etl`` → ``evaluate``, then one short
             ``streaming=True`` fit. Loss finite and falling; the executors
             never imported jax; on several chips the batch and the sharded
             tables live on all of them.
``lm``       ``TransformerLM(attn_impl="flash")`` for three optimizer steps on
             a fixed batch. No estimator path takes ``[B, T]`` token batches
             today, so the step is the jitted ``value_and_grad`` a user would
             write around ``model.apply``, not an invented entry point. The
             params are published through the estimator checkpoint channel
             the replicas load from, with greedy ``attn_impl="full"``
             reference rollouts beside them. On several chips the same steps
             run once more as ``ring_flash`` over ``{"sp": n}``.
``serve``    ``serve.deploy(model, checkpoint_dir, replicas=1, decode on)`` →
             four ``generate`` calls (two of them concurrent) and one
             ``stream`` → tokens equal to the reference rollouts →
             ``close()``. This phase's driver stays off the backend as well:
             the replica actor is the one process that owns the chip.

Token equality in bf16: the replica computes attention with the flash kernels
and the reference with the bf16 einsum, so their logits differ by rounding
(the ``lm`` phase measures by how much) and a near-tie between the two best
tokens could legitimately flip. The prompts are therefore drawn from the seed
as the candidates whose reference rollout keeps its top-2 logit margin above
three times that measured disagreement at every step (twice is what a flip
needs); for those the argmax cannot flip and the comparison is exact equality,
no tolerance.

Every process that uses the device first asserts the platform and prints
platform, device_kind and device count. Without a TPU the script exits
non-zero and prints no result. Every time it prints is a set-up or wall time
of a phase on the device it names. On the chip the last two lines of standard output are JSON:
the summary (phases, set-up and wall times, measured facts, ending in
``"claim": null`` — this is not a benchmark), then the result, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` with
the device as JAX reported it. A run that fails after a phase has seen the TPU
ends in the same object with ``"ok": false``; one that never saw a TPU prints
no JSON at all.
"""
# raydp-lint: disable-file=print-diagnostics  (a standalone smoke narrates to stdout by design: its stdout IS its report)

from __future__ import annotations

import argparse
import dataclasses
import importlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
PHASES = ("kernels", "fit", "lm", "serve")
RUN_TAG_ENV = "RAYDP_TPU_SMOKE_RUN"
DEVICE_FILE = "device.json"  # written by the first process that saw the device
TIME_LIMIT_S = 1140.0  # the contract allows 1200 s, compilation included
SEED = 21


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that differs between the chip run and the CPU rehearsal.
    Widths on the chip are the r05 geometries (ROADMAP's seed cells)."""

    platform: str
    # DLRM / ETL
    vocab_sizes: tuple
    num_dense: int
    embed_dim: int
    mlp: tuple
    batch: int
    rows: int
    epochs: int
    # LM
    vocab: int
    d_model: int
    heads: int
    layers: int
    seq: int
    lm_batch: int
    # serve
    capacity: int
    page: int
    max_seqs: int
    max_new: int
    prompt_lens: tuple
    candidates: int  # reference-rollout candidates per prompt length
    # fence probe
    fence_dim: int
    fence_iters: int


CHIP = Sizes(
    platform="tpu",
    vocab_sizes=(100_000, 10_000, 1_000, 1_000, 100, 100), num_dense=8,
    embed_dim=16, mlp=(128, 64), batch=2048, rows=100_000, epochs=3,
    vocab=2048, d_model=1024, heads=8, layers=4, seq=8192, lm_batch=2,
    capacity=2048, page=128, max_seqs=4, max_new=8,
    prompt_lens=(5, 17, 40, 90, 200), candidates=96,
    fence_dim=8192, fence_iters=400,
)
REHEARSAL = Sizes(
    platform="cpu",
    vocab_sizes=(500, 100, 50), num_dense=4, embed_dim=8, mlp=(16, 8),
    batch=64, rows=2000, epochs=2,
    vocab=64, d_model=64, heads=2, layers=2, seq=128, lm_batch=2,
    capacity=128, page=32, max_seqs=4, max_new=4,
    prompt_lens=(3, 9, 17, 30, 41), candidates=8,
    fence_dim=256, fence_iters=4,
)


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# child side: shared helpers
# ---------------------------------------------------------------------------


class Phase:
    """One child's reporting: lines tagged with the phase, times only on the
    chip, and the result file the parent reads."""

    def __init__(self, name: str, sizes: Sizes, workdir: str):
        self.name = name
        self.sizes = sizes
        self.workdir = workdir
        self.rehearsal = sizes.platform != "tpu"
        self.device: dict = {}
        self.setup_s = 0.0
        self.t0 = time.perf_counter()
        self.facts: dict = {}

    def say(self, msg: str) -> None:
        print(f"[{self.name}] {msg}", flush=True)

    def say_time(self, what: str, seconds: float, kind: str = "set-up") -> None:
        """A rehearsal prints no time, rate or utilization."""
        if not self.rehearsal:
            self.say(
                f"{what}: {kind} time {seconds:.2f} s on "
                f"{self.device['kind']} x{self.device['count']}"
            )

    def claim_device(self) -> dict:
        """First thing any process that uses the device does."""
        import jax

        devices = jax.devices()
        self.device = {
            "platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices),
        }
        self.say(
            f"platform: {self.device['platform']}  device_kind: "
            f"{self.device['kind']}  device count: {self.device['count']}  "
            f"cpu count: {os.cpu_count()}"
        )
        check(
            self.device["platform"] == self.sizes.platform,
            f"jax.devices()[0].platform is {self.device['platform']!r} "
            f"({self.device['kind']}, {self.device['count']} device(s)); this "
            f"run needs {self.sizes.platform!r}"
            + ("" if self.rehearsal else
               " — no accelerator, no result (--rehearse-on-cpu runs the tiny "
               "CPU rehearsal)"),
        )
        # the parent (which never imports jax) reports this device in the
        # result line, also when a later check fails
        with open(os.path.join(self.workdir, DEVICE_FILE), "w") as f:
            json.dump(self.device, f)
        from raydp_tpu.compile_cache import enable_compile_cache

        self.say(f"compile cache: {enable_compile_cache()}")
        return self.device

    def first_call(self, what: str, fn):
        """Run ``fn`` (whose first call compiles), fenced; its wall time is
        this phase's compile set-up."""
        import jax

        t0 = time.perf_counter()
        out = fn()
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        self.setup_s += dt
        self.say_time(f"{what} (compile + first run)", dt)
        return out

    def finish(self) -> None:
        wall = time.perf_counter() - self.t0
        self.say_time("phase", wall, kind="wall")
        with open(os.path.join(self.workdir, f"{self.name}.json"), "w") as f:
            json.dump({
                "device": self.device, "setup_s": round(self.setup_s, 2),
                "wall_s": round(wall, 2), "facts": self.facts,
            }, f)
        self.say("ok")


def require_kernel(ph: Phase, what: str, lowered) -> None:
    """The compiled kernel, not a stand-in: Pallas-on-TPU lowers to a
    ``tpu_custom_call`` custom call carrying the Mosaic module."""
    if ph.rehearsal:
        ph.say(f"{what}: lowers (kernels interpreted in the rehearsal)")
        return
    check("tpu_custom_call" in lowered.as_text(),
          f"{what}: no Mosaic custom call in the "
          "lowered program — a reference path ran in the kernel's place")
    ph.say(f"{what}: Mosaic custom call present in the lowered program")


def rel_err(got, ref) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / max(float(np.abs(ref).max()), 1e-6))


def lm_models(sz: Sizes):
    """The one LM of the run, in its flash (train/serve) and full (reference)
    forms: same parameter tree, different attention arithmetic."""
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM

    kw = dict(
        vocab_size=sz.vocab, d_model=sz.d_model, num_heads=sz.heads,
        num_layers=sz.layers, max_len=sz.seq + 1, dtype=jnp.bfloat16,
    )
    return (
        TransformerLM(attn_impl="flash", **kw),
        TransformerLM(attn_impl="full", **kw),
        kw,
    )


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------


def phase_kernels(ph: Phase) -> None:
    sz = ph.sizes
    ph.claim_device()
    import jax
    import jax.numpy as jnp
    import numpy as np

    fa = importlib.import_module("raydp_tpu.ops.flash_attention")
    inter = importlib.import_module("raydp_tpu.ops.interaction")
    quant = importlib.import_module("raydp_tpu.ops.quantization")
    from raydp_tpu.parallel.ring_attention import full_attention

    failures: list = []

    def close(what: str, got, ref, tol: float) -> None:
        err = rel_err(got, ref)
        finite = bool(np.isfinite(np.asarray(got, np.float32)).all())
        verdict = "ok" if (err <= tol and finite) else "FAIL"
        ph.say(f"{what}: max err / max|ref| = {err:.3g} (tolerance {tol:g}) "
               f"finite={finite} {verdict}")
        if verdict != "ok":
            failures.append(what)

    def exact_ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            return full_attention(
                *(a.astype(jnp.float32) for a in (q, k, v)), causal=True
            )

    rng = np.random.default_rng(SEED)
    b, h, t, d = sz.lm_batch, sz.heads, sz.seq, sz.d_model // sz.heads
    # bf16 operands, f32 accumulation: one bf16 ulp of the largest value is
    # 2^-8 = 3.9e-3; r05 saw ~5e-3 absolute. 1e-2 of max|ref| bounds both.
    tol = 1e-2
    q, k, v, w = (
        jnp.asarray(rng.standard_normal((b, h, t, d)), jnp.bfloat16)
        for _ in range(4)
    )
    ph.say(f"flash attention at B={b} H={h} T={t} D={d} bf16 causal, blocks "
           f"{fa.pick_blocks(t, t, head_dim=d)}")
    sl = (slice(0, 1), slice(0, 2))  # programs are per (batch, head): check two

    def fwd(onepass):
        fn = jax.jit(lambda q, k, v: fa._flash_call(
            q, k, v, 0, 0, True, None, None, None, True, onepass=onepass))
        require_kernel(ph, f"flash fwd onepass={onepass}", fn.lower(q, k, v))
        return ph.first_call(f"flash fwd onepass={onepass}",
                             lambda: fn(q, k, v))

    one, two = fwd(True), fwd(False)
    ref = jax.jit(exact_ref)(q[sl], k[sl], v[sl])
    close("flash fwd vs f32 reference", one[0][sl], ref, tol)
    same = all(
        bool((np.asarray(a, np.float32) == np.asarray(c, np.float32)).all())
        for a, c in zip(one, two)
    )
    ph.say(f"flash fwd one-pass vs two-pass bit-identical: {same}")
    ph.facts["onepass_bit_identical"] = same
    if not same:
        failures.append("one-pass vs two-pass parity")

    def loss(attend):
        return lambda q, k, v, w: (
            attend(q, k, v).astype(jnp.float32) * w.astype(jnp.float32)
        ).sum()

    grad_fn = jax.jit(jax.grad(
        loss(lambda q, k, v: fa.flash_attention(q, k, v, True)),
        argnums=(0, 1, 2)))
    require_kernel(ph, "flash bwd (dq_dkv)", grad_fn.lower(q, k, v, w))
    got = ph.first_call("flash bwd", lambda: grad_fn(q, k, v, w))
    want = jax.jit(jax.grad(loss(exact_ref), argnums=(0, 1, 2)))(
        q[sl], k[sl], v[sl], w[sl])
    for name, g, r in zip(("dq", "dk", "dv"), got, want):
        close(f"flash bwd {name} vs f32 reference", g[sl], r, tol)

    # the backward pass above is ONE fused call (static offsets); offsets that
    # are values of the program, a ring step's, run the two-call pass over
    # the same tiles: the same bits
    lse = one[1] + jnp.log(jnp.maximum(one[2], 1e-30))
    dsum = jnp.sum(w.astype(jnp.float32) * one[0].astype(jnp.float32), axis=-1)
    blocks = jax.jit(lambda q_off, k_off: fa.flash_backward_blocks(
        q, k, v, lse, dsum, w, q_off, k_off, True))
    zero = jnp.int32(0)
    forms = (fa.backward_form(t, t, d), fa.backward_form(t, t, d, q_offset=zero))
    ph.say(f"flash bwd form at static | runtime offsets: {' | '.join(forms)}")
    if forms != ("fused", "two_call"):
        failures.append("backward_form")
    # ... and over the RECTANGULAR grid: the fused call (and the forward
    # above) step over the tiles under the diagonal alone
    tiles = fa.pick_blocks(t, t, d)
    grids = (fa.causal_grid(t, t, *tiles),
             fa.causal_grid(t, t, *tiles, q_offset=zero))
    ph.say(f"flash grid at static | runtime offsets: {' | '.join(grids)} "
           f"(steps a head, live tiles: {fa.causal_steps(t, *tiles)})")
    if grids != ("live", "rectangular:runtime offsets"):
        failures.append("causal_grid")
    two_call = ph.first_call("flash bwd two-call", lambda: blocks(zero, zero))
    fused = ph.first_call("flash bwd fused", lambda: jax.jit(
        lambda: fa.flash_backward_blocks(q, k, v, lse, dsum, w, 0, 0, True))())
    same = all(
        bool((np.asarray(a, np.float32) == np.asarray(c, np.float32)).all())
        for a, c in zip(fused, two_call)
    )
    ph.say(f"flash bwd fused vs two-call bit-identical: {same}")
    ph.facts["fused_backward_bit_identical"] = same
    if not same:
        failures.append("fused vs two-call backward parity")

    # the ring schedule's per-step block product: unnormalized output +
    # (m, l) stats at caller offsets, merged and normalized here
    tq = t // 4
    o_un, m_st, l_st = ph.first_call("flash stats", lambda: jax.jit(
        lambda q, k, v: fa.flash_attention_stats(q, k, v, tq, 0, True)
    )(q[:, :, tq:2 * tq], k[:, :, :2 * tq], v[:, :, :2 * tq]))
    close("flash stats (offset block) vs f32 reference",
          (o_un / l_st[..., None])[sl],
          jax.jit(exact_ref)(q[sl][:, :, :2 * tq], k[sl][:, :, :2 * tq],
                             v[sl][:, :, :2 * tq])[:, :, tq:], tol)

    # decode at the engine's shapes: bf16 query rows against the f32 host
    # cache (default), a bf16 cache, and int8 K/V with per-row scales
    bd, tk = sz.max_seqs, sz.capacity
    lens = jnp.asarray(
        ([1, sz.page + 2, tk // 2, tk] * bd)[:bd], jnp.int32)
    qd = jnp.asarray(rng.standard_normal((bd, h, 1, d)), jnp.bfloat16)
    kc, vc = (jnp.asarray(rng.standard_normal((bd, h, tk, d)), jnp.bfloat16)
              for _ in range(2))

    def decode_ref(q, k, v, lens):
        qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))
        with jax.default_matmul_precision("highest"):
            s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * d ** -0.5
            live = jnp.arange(tk)[None, None, None] < lens[:, None, None, None]
            p = jax.nn.softmax(jnp.where(live, s, -1e30), -1)
            return jnp.einsum("bhqk,bhkd->bhqd", p, vf)

    dref = jax.jit(decode_ref)(qd, kc, vc, lens)
    dec = jax.jit(fa.flash_decode)
    ph.say(f"flash decode at B={bd} H={h} Tq=1 Tk={tk} D={d}, bf16 query")
    for cache_dtype in (jnp.float32, jnp.bfloat16):
        args = (qd, kc.astype(cache_dtype), vc.astype(cache_dtype), lens)
        name = f"flash decode {jnp.dtype(cache_dtype).name} cache"
        require_kernel(ph, name, dec.lower(*args))
        close(f"{name} vs f32 reference",
              ph.first_call(name, lambda: dec(*args)), dref, tol)

    def to_int8(x):
        vals, scales = quant.quantize_int8(
            x.astype(jnp.float32).reshape(-1, d))
        return vals.reshape(x.shape), scales.reshape(x.shape[:-1])

    (k8, ks), (v8, vs) = to_int8(kc), to_int8(vc)
    dec8 = jax.jit(lambda q, k, v, n, a, c: fa.flash_decode(
        q, k, v, n, k_scale=a, v_scale=c))
    require_kernel(ph, "flash decode int8 cache",
                   dec8.lower(qd, k8, v8, lens, ks, vs))
    o8 = ph.first_call("flash decode int8 cache",
                       lambda: dec8(qd, k8, v8, lens, ks, vs))
    # int8 rounding moves K/V by scale/2 <= max|row|/254 per element
    close("flash decode int8 cache vs unquantized f32 reference", o8, dref,
          4 * tol)
    o_dq = dec(qd, k8.astype(jnp.float32) * ks[..., None],
               v8.astype(jnp.float32) * vs[..., None], lens)
    inline = bool((np.asarray(o8, np.float32)
                   == np.asarray(o_dq, np.float32)).all())
    ph.say(f"int8 in-kernel dequant == f32 kernel on the dequantized cache, "
           f"bitwise: {inline}")
    if not inline:
        failures.append("int8 inline dequant parity")

    # decode-vs-prefill: what docs/serving.md may say about bit identity
    parity = {}
    for dtype, (pb, phh, pt, pd) in (
        (jnp.float32, (2, 3, 128, 32)),  # tests/test_flash_decode.py's shape
        (jnp.float32, (1, h, tk, d)),
        (jnp.bfloat16, (1, h, tk, d)),
    ):
        qf, kf, vf = (jnp.asarray(rng.standard_normal((pb, phh, pt, pd)),
                                  dtype) for _ in range(3))
        pre = jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, True))(
            qf, kf, vf)
        worst = 0.0
        for n in (17, pt // 2, pt):
            step = dec(qf[:, :, n - 1:n], kf.astype(jnp.float32),
                       vf.astype(jnp.float32), jnp.full((pb,), n, jnp.int32))
            worst = max(worst, float(np.abs(
                np.asarray(step, np.float32)
                - np.asarray(pre[:, :, n - 1:n], np.float32)).max()))
        key = f"{jnp.dtype(dtype).name} {pb}x{phh}x{pt}x{pd}"
        parity[key] = worst
        ph.say(f"decode step vs prefill row, {key}: max abs diff {worst:.3g}"
               f" ({'bit-identical' if worst == 0 else 'NOT bit-identical'})")
    ph.facts["decode_vs_prefill_max_abs_diff"] = parity

    # DLRM interaction at the fit's shapes. f32 operands on the MXU's default
    # precision are bf16 passes, in the kernel and in the einsum it replaces
    stacked = jnp.asarray(rng.standard_normal(
        (sz.batch, 1 + len(sz.vocab_sizes), sz.embed_dim)), jnp.float32)
    ifn = jax.jit(inter.dot_interaction_pallas)
    require_kernel(ph, "dot interaction", ifn.lower(stacked))
    close("dot interaction vs einsum",
          ph.first_call("dot interaction", lambda: ifn(stacked)),
          jax.jit(inter.dot_interaction)(stacked), tol)
    close("dot interaction grad vs einsum grad",
          jax.jit(jax.grad(lambda s: (inter.dot_interaction_pallas(s) ** 2)
                           .sum()))(stacked),
          jax.jit(jax.grad(lambda s: (inter.dot_interaction(s) ** 2).sum()))(
              stacked), tol)

    # row write-back into a table of the DLRM fit (its rows fill no whole
    # number of 128-row blocks), a parameter and its state in one call:
    # against XLA's scatter, bit for bit
    from raydp_tpu.estimator.row_update import sorted_unique
    from raydp_tpu.ops.row_write_back import row_write_back

    size = max(sz.vocab_sizes) - 3
    uniq, _ = sorted_unique(jnp.asarray(np.concatenate([
        rng.integers(0, size, sz.batch - 2), [size - 1, 0]])[None], jnp.int32),
        [size])
    tables = [jnp.asarray(rng.standard_normal((size, sz.embed_dim)),
                          jnp.float32) for _ in range(2)]
    new = [jnp.asarray(rng.standard_normal((sz.batch, sz.embed_dim)),
                       jnp.float32) for _ in range(2)]
    # the same rows read out of the same tables (ops/row_gather.py), first:
    # against XLA's gather, bit for bit at every slot whose id is a row
    from raydp_tpu.ops.row_gather import row_gather

    gfn = jax.jit(row_gather)
    require_kernel(ph, "row gather", gfn.lower(tables, uniq[0]))
    read = ph.first_call("row gather", lambda: gfn(tables, uniq[0]))
    live = np.asarray(uniq[0]) < size
    same = all(
        np.array_equal(np.asarray(g)[live], np.asarray(
            t.at[uniq[0]].get(mode="clip"))[live])
        for g, t in zip(read, tables))
    ph.say(f"row gather vs XLA's gather, {size} rows of {sz.embed_dim}: "
           f"{'bit-identical' if same else 'NOT bit-identical FAIL'}")
    if not same:
        failures.append("row gather")

    wfn = jax.jit(row_write_back)
    require_kernel(ph, "row write-back", wfn.lower(tables, new, uniq[0]))
    got = ph.first_call("row write-back", lambda: wfn(tables, new, uniq[0]))
    same = all(
        np.array_equal(np.asarray(g), np.asarray(t.at[uniq[0]].set(
            r, mode="drop"))) for g, t, r in zip(got, tables, new))
    ph.say(f"row write-back vs scatter, {size} rows of {sz.embed_dim}: "
           f"{'bit-identical' if same else 'NOT bit-identical FAIL'}")
    if not same:
        failures.append("row write-back")

    # stochastic int8 quantize: the Pallas PRNG kernel on a TPU
    x = jnp.asarray(rng.standard_normal((sz.batch, 128)), jnp.float32) * 3.0
    sums = []
    for seed in (1, 2):
        qfn = jax.jit(lambda x: quant.quantize_int8(
            x, seed=seed, stochastic=True))
        if not ph.rehearsal:  # off-TPU this op is jax.random by design
            require_kernel(ph, f"stochastic quantize seed={seed}",
                           qfn.lower(x))
        vals, scales = ph.first_call(f"stochastic quantize seed={seed}",
                                     lambda: qfn(x))
        vals, scales = np.asarray(vals, np.float32), np.asarray(scales)
        err = (vals * scales - np.asarray(x)) / scales
        # unbiased: the mean of N rounding errors, each uniform on (-1, 1)
        # about 0 with sd 0.29..0.41, stays within 6 sd / sqrt(N) of zero
        bias_bound = 6 * 0.41 / err.size ** 0.5 + 1e-3
        ok = bool(np.abs(err).max() < 1.0 and abs(err.mean()) < bias_bound
                  and np.allclose(scales[:, 0],
                                  np.abs(np.asarray(x)).max(-1) / 127.0))
        ph.say(f"stochastic quantize seed={seed}: |err| < 1 quantum "
               f"(max {np.abs(err).max():.3f}), mean err {err.mean():.2e} "
               f"quanta {'ok' if ok else 'FAIL'}")
        if not ok:
            failures.append(f"stochastic quantize seed={seed}")
        sums.append(float(vals.sum()))
    check(sums[0] != sums[1], "stochastic quantize ignores its seed")

    # fences: does block_until_ready wait for the device on this machine?
    @jax.jit
    def burn(a):
        return jax.lax.fori_loop(
            0, sz.fence_iters,
            lambda i, a: (a @ a) * (1.0 / sz.fence_dim) + 0.001, a)

    a = jnp.ones((sz.fence_dim, sz.fence_dim), jnp.bfloat16)
    ph.first_call("fence probe", lambda: burn(a))
    t0 = time.perf_counter()
    jax.block_until_ready(burn(a))
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(burn(a)[0, 0])
    t_fetch = time.perf_counter() - t0
    if not ph.rehearsal:
        ph.say(f"fence probe: block_until_ready wall time {t_block:.4f} s, "
               f"value-fetch wall time {t_fetch:.4f} s on "
               f"{ph.device['kind']} x{ph.device['count']}")
        ph.facts["fence_block_s"] = round(t_block, 4)
        ph.facts["fence_fetch_s"] = round(t_fetch, 4)
        check(abs(t_block - t_fetch) <= 0.05 * t_fetch,
              "block_until_ready and a value fetch disagree on this machine:"
              " every fenced time in the repo would be wrong")

    check(not failures, "kernel checks failed: " + "; ".join(failures))


# ---------------------------------------------------------------------------
# phase: fit
# ---------------------------------------------------------------------------


def criteo_source(sz: Sizes):
    """Criteo-shaped rows from the seed, with a label the model can learn."""
    import numpy as np
    import pandas as pd

    rng = np.random.default_rng(SEED)
    data = {}
    for i in range(sz.num_dense):
        data[f"i{i}"] = rng.integers(0, 1000, sz.rows).astype(np.float32)
    for j, vocab in enumerate(sz.vocab_sizes):
        data[f"c{j}"] = rng.integers(0, vocab, sz.rows).astype(np.int64)
    last = f"c{len(sz.vocab_sizes) - 1}"
    logit = 3.0 * (data["i0"] / 1000.0 - 0.5) + 2.0 * (data[last] % 2 - 0.5)
    data["label"] = (
        rng.random(sz.rows) < 1.0 / (1.0 + np.exp(-2.0 * logit))
    ).astype(np.float32)
    return pd.DataFrame(data)


def _executor_report(table):
    """Runs inside an ETL executor (``map_batches``): did this process ever
    import jax? An executor that had would contend for the driver's chip."""
    import pyarrow as pa

    return pa.table({
        "pid": [os.getpid()], "has_jax": ["jax" in sys.modules],
    })


def phase_fit(ph: Phase) -> None:
    sz = ph.sizes
    dev = ph.claim_device()
    import jax
    import numpy as np

    import raydp_tpu
    from raydp_tpu.estimator import JaxEstimator
    from raydp_tpu.estimator.jax_estimator import _LOSSES
    from raydp_tpu.etl import functions as F
    from raydp_tpu.exchange import dataframe_to_dataset
    from raydp_tpu.exchange.jax_io import device_put_batch
    from raydp_tpu.models import DLRM, dlrm_sharding_rules
    from raydp_tpu.parallel import make_mesh

    n = dev["count"]
    cores = max(1, min(4, ((os.cpu_count() or 1) - 2) // 4))
    model_axis = 2 if n % 2 == 0 else 1
    mesh = make_mesh({"data": n // model_axis, "model": model_axis})
    ph.say(f"mesh {dict(mesh.shape)} over {n} device(s); 2 executors x "
           f"{cores} core(s)")

    session = raydp_tpu.init_etl(
        "chip-smoke", num_executors=2, executor_cores=cores,
        executor_memory="1G",
    )
    df = session.from_pandas(criteo_source(sz), num_partitions=4 * cores)
    dense_cols = [f"i{i}" for i in range(sz.num_dense)]
    cat_cols = [f"c{j}" for j in range(len(sz.vocab_sizes))]
    for col in dense_cols:
        df = df.with_column(col, F.log1p(F.col(col)).cast("float32"))
    for col, vocab in zip(cat_cols, sz.vocab_sizes):
        df = df.with_column(col, F.hash(col, vocab).cast("int32"))
    train_df, test_df = df.random_split([0.9, 0.1], seed=0)

    def estimator(**kw):
        return JaxEstimator(
            # True, not None: the rehearsal runs the same (interpreted)
            # kernel the chip compiles, instead of the einsum
            model=DLRM(
                vocab_sizes=sz.vocab_sizes, num_dense=sz.num_dense,
                embed_dim=sz.embed_dim, bottom_mlp=sz.mlp, top_mlp=sz.mlp,
                use_pallas_interaction=True,
            ),
            optimizer="adam", loss="bce", learning_rate=3e-3,
            feature_columns=dense_cols + cat_cols,
            categorical_columns=cat_cols, label_column="label",
            batch_size=sz.batch, seed=SEED, mesh=mesh,
            param_sharding_rules=dlrm_sharding_rules(), **kw,
        )

    est = estimator(num_epochs=sz.epochs)
    history = est.fit_on_etl(train_df, test_df)
    ph.setup_s += est.compile_seconds_
    ph.say_time("DLRM fit compile", est.compile_seconds_)
    losses = [float(r["train_loss"]) for r in history]
    ph.say(f"DLRM train loss by epoch: {[round(x, 4) for x in losses]}; "
           f"eval loss {[round(float(r['eval_loss']), 4) for r in history]}")
    check(all(np.isfinite(losses)), "DLRM loss not finite")
    check(losses[-1] < losses[0], f"DLRM loss did not fall: {losses}")
    ph.facts["dlrm_loss"] = [round(x, 5) for x in losses]

    metrics = est.evaluate(dataframe_to_dataset(test_df))
    ph.say(f"evaluate: {metrics}")
    check(np.isfinite(metrics["eval_loss"]), "evaluate loss not finite")

    stats = est.fit_stats_
    ph.say(f"peak table: device_kind={stats['device_kind']!r} "
           f"peak_source={stats['peak_source']}")
    check(stats["device_kind"] == dev["kind"], "estimator saw another device")
    if not ph.rehearsal:
        check(stats["peak_source"] == "tpu-table",
              f"peak_source {stats['peak_source']!r}, need 'tpu-table'")

    # the compiled kernel, at the fit's shapes, under the fit's mesh context
    fitted = est.get_model()
    x = (np.zeros((sz.batch, sz.num_dense), np.float32),
         np.zeros((sz.batch, len(cat_cols)), np.int32))
    y = np.zeros((sz.batch,), np.float32)
    with jax.set_mesh(mesh):
        lowered = jax.jit(jax.value_and_grad(
            lambda p, x, y: _LOSSES["bce"](fitted.module.apply(p, x), y)
        )).lower(fitted.params, device_put_batch(x, mesh),
                 device_put_batch(y, mesh))
    require_kernel(ph, "DLRM loss and gradient at the fit's shapes", lowered)
    if n > 1:
        # shard_map lowers to a manual computation; the einsum has none
        check("sdy.manual_computation" in lowered.as_text(),
              "multi-device DLRM step took the einsum, not the shard_map "
              "branch of interaction_fused")
        table = fitted.params["params"]["embedding_0"]
        on = {s.device for s in table.addressable_shards}
        check(len(on) == n, f"embedding_0 lives on {len(on)}/{n} devices")
        check(table.addressable_shards[0].data.shape[0]
              == sz.vocab_sizes[0] // model_axis,
              "embedding_0 is not vocab-sharded over the model axis")
        batch_on = {s.device for s in
                    device_put_batch(x, mesh)[0].addressable_shards}
        check(len(batch_on) == n, f"batch lives on {len(batch_on)}/{n} devices")
        ph.say(f"sharded tables and batch have addressable shards on all "
               f"{n} devices")

    # the path every dataset larger than HBM takes
    stream = estimator(num_epochs=1, streaming=True)
    stream_hist = stream.fit_on_etl(train_df)
    ph.setup_s += stream.compile_seconds_
    ph.say_time("streaming fit compile", stream.compile_seconds_)
    stream_loss = float(stream_hist[-1]["train_loss"])
    ph.say(f"streaming fit: train loss {stream_loss:.4f}")
    check(np.isfinite(stream_loss), "streaming fit loss not finite")
    check(stream_loss < 0.6932, "streaming fit learned nothing (loss >= ln 2)")

    report = (
        session.range(0, 64, num_partitions=8 * cores)
        .map_batches(_executor_report).to_pandas()
    )
    pids = sorted(set(int(p) for p in report["pid"]))
    check(os.getpid() not in pids, "the probe ran in the driver")
    check(not bool(report["has_jax"].any()),
          "an ETL executor imported jax: it would contend for the chip")
    ph.say(f"ETL executors {pids} never imported jax")

    raydp_tpu.stop_etl()
    from raydp_tpu.cluster import api as cluster

    cluster.shutdown()


# ---------------------------------------------------------------------------
# phase: lm
# ---------------------------------------------------------------------------


def phase_lm(ph: Phase) -> None:
    sz = ph.sizes
    dev = ph.claim_device()
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from raydp_tpu.estimator import JaxEstimator
    from raydp_tpu.models import TransformerLM, sequence_parallel_apply
    from raydp_tpu.parallel import make_mesh
    from raydp_tpu.serve.decode import DecodeEngine

    flash, full, kw = lm_models(sz)
    rng = np.random.default_rng(SEED)
    # the fixed batch: each row walks one full-period permutation of the
    # vocabulary (an LCG; vocab is a power of two), so every token is a
    # target equally often. On uniformly random tokens three Adam steps
    # mostly learn the batch's most frequent token, and every greedy
    # rollout collapses onto it — equal tokens would then prove little.
    tokens = np.empty((sz.lm_batch, sz.seq + 1), np.int32)
    tokens[:, 0] = rng.integers(0, sz.vocab, sz.lm_batch)
    for t in range(sz.seq):
        tokens[:, t + 1] = (5 * tokens[:, t] + 3) % sz.vocab
    tok, tgt = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    params0 = jax.jit(flash.init)(jax.random.PRNGKey(SEED), tok)
    tx = optax.adam(3e-4)

    def train(apply_logits, what):
        def step(params, opt_state, tok, tgt):
            def compute(p):
                return optax.softmax_cross_entropy_with_integer_labels(
                    apply_logits(p, tok), tgt).mean()

            loss, grads = jax.value_and_grad(compute)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        step = jax.jit(step)
        params, opt_state = params0, tx.init(params0)
        require_kernel(ph, f"{what} step",
                       step.lower(params, opt_state, tok, tgt))
        losses = []
        for i in range(3):
            if i == 0:
                params, opt_state, loss = ph.first_call(
                    f"{what} step", lambda: step(params, opt_state, tok, tgt))
            else:
                params, opt_state, loss = step(params, opt_state, tok, tgt)
            losses.append(float(loss))
        ph.say(f"{what}: loss over three steps on a fixed batch "
               f"{[round(x, 4) for x in losses]}")
        check(all(np.isfinite(losses)), f"{what} loss not finite")
        check(losses[2] < losses[0], f"{what} loss did not fall: {losses}")
        return params, losses

    ph.say(f"TransformerLM vocab={sz.vocab} d_model={sz.d_model} "
           f"heads={sz.heads}x{sz.d_model // sz.heads} layers={sz.layers} "
           f"bf16, B={sz.lm_batch} T={sz.seq}")
    params, flash_losses = train(flash.apply, "LM flash")
    ph.facts["lm_loss"] = [round(x, 5) for x in flash_losses]

    n = dev["count"]
    if n > 1:
        # the multi-chip LM path the repo has: the ring schedule with the
        # flash kernel per step, sequence split over every chip
        mesh = make_mesh({"sp": n})
        ring = TransformerLM(attn_impl="ring_flash", seq_axis="sp", **kw)
        _, ring_losses = train(
            lambda p, t: sequence_parallel_apply(ring, p, t, mesh),
            f"LM ring_flash over sp={n}")
        worst = max(abs(a - c) / abs(a)
                    for a, c in zip(flash_losses, ring_losses))
        ph.say(f"ring_flash vs one-chip flash loss: max relative difference "
               f"{worst:.3g} (tolerance 0.02)")
        check(worst <= 0.02, "ring_flash loss disagrees with flash")
        ph.facts["ring_loss"] = [round(x, 5) for x in ring_losses]

    # publish through the channel the replicas load from
    ckpt = os.path.join(ph.workdir, "lm-ckpt")
    JaxEstimator(model=flash, checkpoint_dir=ckpt)._save_checkpoint(
        params, 0, {})
    ph.say(f"params published to {ckpt}")

    # the replica's own prefill and decode-step programs (the engine's jits,
    # not copies of them) at the serving shapes
    head_dim = sz.d_model // sz.heads
    with DecodeEngine(
        flash, params, capacity_tokens=sz.capacity, page_tokens=sz.page,
        max_seqs=sz.max_seqs, max_new_tokens=sz.max_new,
    ) as engine:
        sds = jax.ShapeDtypeStruct
        cache = sds((sz.max_seqs, sz.heads, sz.capacity, head_dim),
                    jnp.float32)
        require_kernel(ph, "serving prefill", engine._prefill_fn.lower(
            params, sds((1, sz.capacity), jnp.int32)))
        require_kernel(ph, "serving decode step", engine._decode_fn.lower(
            params, sds((sz.max_seqs, 1), jnp.int32),
            sds((sz.max_seqs,), jnp.int32), [(cache, cache)] * sz.layers))

    # greedy reference rollouts (attn_impl="full"), for candidate prompts at
    # each length; keep per length the one whose top-2 margin stays widest
    pad = 1 << (max(sz.prompt_lens) + sz.max_new - 1).bit_length()
    lens0 = np.repeat(np.asarray(sz.prompt_lens), sz.candidates)
    count = len(lens0)
    toks = np.zeros((count, pad), np.int32)
    for row, length in enumerate(lens0):
        toks[row, :length] = rng.integers(0, sz.vocab, length)

    def last_logits(model):
        return jax.jit(lambda p, toks, lens: model.apply(p, toks)[
            jnp.arange(count), lens - 1])

    ref_fn, flash_fn = last_logits(full), last_logits(flash)
    lens = lens0.copy()
    margin = np.full(count, np.inf)
    noise = 0.0
    rolled = [[] for _ in range(count)]
    for _ in range(sz.max_new):
        ref_logits = ref_fn(params, jnp.asarray(toks), jnp.asarray(lens))
        flash_logits = flash_fn(params, jnp.asarray(toks), jnp.asarray(lens))
        noise = max(noise, float(jnp.abs(ref_logits - flash_logits).max()))
        top, ids = jax.lax.top_k(ref_logits, 2)
        top, ids = np.asarray(top), np.asarray(ids)
        margin = np.minimum(margin, top[:, 0] - top[:, 1])
        for row in range(count):
            rolled[row].append(int(ids[row, 0]))
            toks[row, lens[row]] = ids[row, 0]
        lens = lens + 1
    # two logits that each move by at most `noise` swap order only if they
    # were closer than 2 x noise; 3 x leaves half as much again for the
    # decode kernel's rounding, which the prefill comparison does not see
    need = 3.0 * noise
    ph.say(f"flash vs full logits disagree by at most {noise:.4g}; prompts "
           f"need a top-2 margin above {need:.4g} at every step")
    rollouts = []
    for i, length in enumerate(sz.prompt_lens):
        rows = [r for r in range(i * sz.candidates, (i + 1) * sz.candidates)
                if margin[r] > need]
        check(bool(rows),
              f"no candidate prompt of length {length} keeps its top-2 "
              f"margin above {need:.4g}")
        # of those, the rollout that says the most: distinct tokens first
        best = max(rows, key=lambda r: (len(set(rolled[r])), margin[r]))
        rollouts.append({
            "prompt": [int(t) for t in toks[best, :length]],
            "tokens": rolled[best], "min_margin": float(margin[best]),
        })
        ph.say(f"prompt of {length} tokens: reference rollout "
               f"{rolled[best]} (min margin {margin[best]:.4g})")
    with open(os.path.join(ph.workdir, "rollouts.json"), "w") as f:
        json.dump({"checkpoint_dir": ckpt, "rollouts": rollouts,
                   "logit_noise": noise}, f)


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------


def _holds_accelerator() -> list:
    """Device nodes this process has open: empty while it is off the chip."""
    targets = (os.path.realpath(f"/proc/self/fd/{fd}")
               for fd in os.listdir("/proc/self/fd"))
    return [t for t in targets if t.startswith(("/dev/vfio", "/dev/accel"))]


def phase_serve(ph: Phase) -> None:
    """The driver of this phase never touches the backend (it builds the
    model description, which imports jax, and creates no array): the replica
    actor asserts the platform and owns the chip."""
    sz = ph.sizes
    from raydp_tpu import serve
    from raydp_tpu.cluster import api as cluster

    with open(os.path.join(ph.workdir, "rollouts.json")) as f:
        published = json.load(f)
    rollouts = published["rollouts"]
    flash, _, _ = lm_models(sz)

    t0 = time.perf_counter()
    dep = serve.deploy(
        model=flash, checkpoint_dir=published["checkpoint_dir"], replicas=1,
        platform=sz.platform, conf={
            "serve.decode.enabled": True,
            "serve.decode.capacity_tokens": sz.capacity,
            "serve.decode.page_tokens": sz.page,
            "serve.decode.max_seqs": sz.max_seqs,
            "serve.decode.max_new_tokens": sz.max_new,
        },
    )
    try:
        info = dep.infos()[0]
        ph.device = {"platform": info["platform"],
                     "kind": info["device_kind"],
                     "count": info["device_count"]}
        ph.say(f"replica pid {info['pid']} platform: {info['platform']}  "
               f"device_kind: {info['device_kind']}  device count: "
               f"{info['device_count']}")
        check(info["platform"] == sz.platform, f"replica serves from {info}")
        ph.say_time("replica start (backend init + checkpoint load)",
                    time.perf_counter() - t0)

        def generate(i):
            return dep.generate(rollouts[i]["prompt"], sz.max_new, timeout=600)

        # two concurrent requests: the continuous batcher must hold both
        t_first = time.perf_counter()
        inflight = 0
        with ThreadPoolExecutor(max_workers=2) as pool:
            pair = [pool.submit(generate, i) for i in (0, 1)]
            while not all(f.done() for f in pair):
                stats = dep.decode_stats()[0]
                inflight = max(inflight, int(stats.get("inflight", 0)))
                time.sleep(0.01)
            served = [f.result() for f in pair]
        ph.setup_s += time.perf_counter() - t_first
        ph.say_time("first two requests (prefill + decode-step compile in "
                    "the replica, then decode)", time.perf_counter() - t_first)
        check(inflight >= 2, f"concurrent requests never shared a decode "
              f"round (max inflight {inflight})")
        ph.say(f"continuous batcher held {inflight} sequences at once")
        served += [generate(2), generate(3)]
        served.append(list(dep.stream(rollouts[4]["prompt"], sz.max_new,
                                      timeout=600)))

        for i, want in enumerate(rollouts):
            ph.say(f"request {i} (prompt {len(want['prompt'])} tokens): "
                   f"served {served[i]}")
            check(served[i] == want["tokens"],
                  f"request {i}: served {served[i]} != reference "
                  f"{want['tokens']} (min margin {want['min_margin']:.4g}, "
                  f"logit noise {published['logit_noise']:.4g})")
        ph.say(f"{len(rollouts)} requests x {sz.max_new} tokens equal the "
               "greedy reference rollouts exactly")

        # one process per chip: a second replica PROCESS cannot take a chip
        # the first holds, and must say so instead of serving from the CPU
        dep.scale_to(2)
        if sz.platform == "tpu":
            check(dep.replica_count() == 1,
                  "a second replica process came up beside the one that "
                  "holds the chip")
            ph.say("second replica process refused (its backend-init error "
                   "is the spawn failure logged above)")
        dep.scale_to(1)
    finally:
        dep.close()
        cluster.shutdown()
    held = _holds_accelerator()
    check(not held, f"the serve driver opened the accelerator: {held}")
    ph.say("driver stayed off the accelerator")


CHILD_PHASES = {
    "kernels": phase_kernels, "fit": phase_fit, "lm": phase_lm,
    "serve": phase_serve,
}


def run_child(name: str, rehearsal: bool, workdir: str) -> int:
    ph = Phase(name, REHEARSAL if rehearsal else CHIP, workdir)
    try:
        CHILD_PHASES[name](ph)
    except SmokeFailure as exc:
        ph.say(f"FAILED: {exc}")
        return 1
    ph.finish()
    return 0


# ---------------------------------------------------------------------------
# parent side: no JAX here
# ---------------------------------------------------------------------------


def tagged_pids(tag: str) -> list:
    """Live processes started by this run (they inherit its tag; a
    zygote-forked actor shows the zygote's environment, which has it)."""
    needle = f"{RUN_TAG_ENV}={tag}".encode()
    found = []
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            with open(f"/proc/{entry}/environ", "rb") as f:
                env = f.read()
            with open(f"/proc/{entry}/stat") as f:
                state = f.read().rsplit(")", 1)[1].split()[0]
        except OSError:  # raydp-lint: disable=swallowed-exceptions (the process exited between listdir and open: gone is what the sweep wants)
            continue
        if needle in env.split(b"\0") and state != "Z":
            found.append(int(entry))
    return found


def sweep(tag: str, grace_s: float) -> list:
    """Wait for the run's processes to exit; kill what is left. Returns the
    pids that had to be killed."""
    deadline = time.monotonic() + grace_s
    while True:
        alive = tagged_pids(tag)
        if not alive or time.monotonic() > deadline:
            break
        time.sleep(0.1)
    for pid in alive:
        try:
            os.kill(pid, 9)
        except ProcessLookupError:  # raydp-lint: disable=swallowed-exceptions (exited on its own since the listing; still reported as left over)
            pass
    return alive


def result_line(ok: bool, device: dict) -> str:
    """The run's last line of standard output: exactly these keys, the
    device as jax reported it to the first process that claimed it."""
    return json.dumps({"ok": ok, "device": {
        "platform": str(device["platform"]), "kind": str(device["kind"]),
        "count": int(device["count"]),
    }})


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--rehearse-on-cpu", action="store_true",
        help="tiny sizes on the CPU backend with interpreted kernels: proves "
             "the control flow, prints no time and is not a chip result")
    parser.add_argument("--phase", choices=PHASES, help=argparse.SUPPRESS)
    parser.add_argument("--workdir", help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.phase:
        return run_child(args.phase, args.rehearse_on_cpu, args.workdir)

    if not os.path.isfile(os.path.join(HERE, "raydp_tpu", "__init__.py")):
        print("chip_smoke.py must sit at the root of a raydp-tpu checkout: "
              f"no raydp_tpu package in {HERE}", file=sys.stderr)
        return 2
    if "jax" in sys.modules:
        raise AssertionError("the smoke's parent must never import jax")

    rehearsal = args.rehearse_on_cpu
    prefix = "[REHEARSAL on cpu — not a chip run] " if rehearsal else ""
    tag = uuid.uuid4().hex
    workdir = tempfile.mkdtemp(prefix="chip-smoke-")
    env = dict(os.environ)
    env[RUN_TAG_ENV] = tag
    # a machine-global zygote would outlive the run (1800 s idle TTL); the
    # session-local one dies with the phase that started it
    env["RAYDP_TPU_NO_GLOBAL_ZYGOTE"] = "1"
    env["PYTHONUNBUFFERED"] = "1"
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
    log_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(log_dir, exist_ok=True)
    t_start = time.monotonic()
    results: dict = {}
    failed = None
    with open(os.path.join(log_dir, "chip_smoke.log"), "w") as log:

        def emit(line: str) -> None:
            print(prefix + line, flush=True)
            log.write(prefix + line + "\n")
            log.flush()

        try:
            for name in PHASES:
                remaining = TIME_LIMIT_S - (time.monotonic() - t_start)
                cmd = [sys.executable, os.path.abspath(__file__),
                       "--phase", name, "--workdir", workdir]
                if rehearsal:
                    cmd.append("--rehearse-on-cpu")
                proc = subprocess.Popen(
                    cmd, cwd=HERE, env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT, text=True, errors="replace",
                    start_new_session=True,
                )
                killer = threading.Timer(max(remaining, 1.0), proc.kill)
                killer.start()
                try:
                    for line in proc.stdout:
                        emit(line.rstrip("\n"))
                    code = proc.wait()
                finally:
                    killer.cancel()
                result_path = os.path.join(workdir, f"{name}.json")
                if code != 0 or not os.path.exists(result_path):
                    failed = f"phase {name} failed (exit code {code})"
                    break
                with open(result_path) as f:
                    results[name] = json.load(f)
                # what the phase started is gone before the next one needs
                # the chip
                left = sweep(tag, grace_s=20.0)
                if left:
                    failed = (f"phase {name} left processes alive: {left} "
                              "(killed)")
                    break
        finally:
            leftover = sweep(tag, grace_s=5.0 if failed else 20.0)
            seen = None  # the accelerator a phase saw, as jax reported it
            device_path = os.path.join(workdir, DEVICE_FILE)
            if os.path.exists(device_path):
                with open(device_path) as f:
                    seen = json.load(f)
            shutil.rmtree(workdir, ignore_errors=True)
        if failed is None and leftover:
            failed = f"processes left alive at the end: {leftover} (killed)"
        if failed is None:
            devices = {json.dumps(r["device"], sort_keys=True)
                       for r in results.values()}
            if len(devices) != 1:
                failed = f"phases saw different devices: {sorted(devices)}"
        if failed is not None:
            if seen is None or rehearsal:
                emit(f"chip_smoke: {failed} — no result")
            else:
                # the run reached the accelerator and then failed: say so in
                # the result's own format. Without an accelerator: no JSON.
                emit(f"chip_smoke: {failed}")
                line = result_line(False, seen)
                log.write(line + "\n")
                print(line, flush=True)
            return 1
        emit("chip_smoke: all phases passed; no process of the run is alive")

        device = results[PHASES[0]]["device"]
        if rehearsal:
            lines = [json.dumps({
                "rehearsal": True, "ok": True, "device": device,
                "phases": list(results), "claim": None,
            })]
        else:
            lines = [
                json.dumps({
                    "ok": True, "device": device,
                    "phases": {
                        name: {"wall_s": r["wall_s"],
                               "compile_setup_s": r["setup_s"]}
                        for name, r in results.items()
                    },
                    "facts": {k: v for r in results.values()
                              for k, v in r["facts"].items()},
                    "claim": None,
                }),
                result_line(True, device),
            ]
        for line in lines:
            log.write(line + "\n")
    for line in lines:
        print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
