"""Model replica actors: the serving plane's unit of capacity.

A replica is an ordinary cluster actor (zygote-warm-forked like every light
actor — set ``RAYDP_TPU_ZYGOTE_WARM_JAX=1`` before the first ``cluster.init``
on a machine to bake the jax/flax/orbax import set into the fork template and
make replica spin-up fork-bound) that

- loads a ``JaxEstimator`` checkpoint through the estimator's INFERENCE
  loading path (``load_latest_checkpoint``: params only, no optimizer state,
  nothing fit-oriented),
- holds an AOT-compiled inference jit per (model fingerprint, batch-shape
  bucket) — the exact executor-resident-program shape of the PR 6 compiled
  ETL plane: the batcher pads every dispatch to a configured bucket, so the
  cache stays small and every bucket's numerics are bit-stable (XLA lowers
  per shape; at a FIXED shape per-row results are independent of batch
  composition, which is what makes kill/no-kill byte-identity gates honest),
- swaps (fingerprint, params, compiled-cache) ATOMICALLY on ``reload``: the
  old jit serves every in-flight and concurrent request until the new
  weights are restored AND compiled warm, so a rolling checkpoint reload
  never serves half-loaded state.

Inference is pure and stateless between requests — a re-dispatched request
(replica SIGKILLed mid-flight) recomputes the identical answer, which is the
whole basis of the batcher's zero-drop re-admission.
"""

from __future__ import annotations

import hashlib
import threading
from dataclasses import dataclass, field
from typing import Any, Optional, Tuple

import numpy as np

from raydp_tpu.exchange.features import f0, fmap


@dataclass
class ReplicaSpec:
    """Everything a replica process needs to build its model and serve it.
    Travels cloudpickled inside the actor spawn spec; deliberately holds NO
    trained weights — the checkpoint directory is the weight channel, which
    is what makes rolling reload and post-crash respawn trivially correct."""

    model: Any  # flax Module instance or zero-arg creator fn
    checkpoint_dir: str
    buckets: Tuple[int, ...] = (1, 2, 4, 8, 16, 32, 64)
    # optional example feature row(s): lets the replica AOT-compile every
    # bucket at load time (boot and reload both), so no request ever pays a
    # compile. Without it buckets compile lazily on first use.
    example: Any = None
    name: str = "default"
    extra_estimator_kwargs: dict = field(default_factory=dict)
    # DecodeEngine kwargs (serve/decode.py) — non-empty enables the
    # decode_submit/decode_poll streaming surface on each replica
    decode: dict = field(default_factory=dict)
    # the platform the driver asked to serve from ("tpu", "cpu", ...): a
    # replica that comes up on any other is a FAILED spawn. A chip belongs
    # to one process; a second replica process on the same chip either
    # fails backend init (JAX_PLATFORMS names tpu) or — JAX_PLATFORMS unset
    # — lands on the CPU backend without a word. None = serve from whatever
    # JAX picked.
    platform: Optional[str] = None


class _ModelState:
    """One immutable generation of servable state. ``infer`` reads the
    replica's ``_active`` reference once and works off this object alone, so
    a concurrent reload (which builds a whole new _ModelState and swaps the
    reference) can never expose a torn view."""

    __slots__ = (
        "fingerprint", "epoch", "step", "params", "jitted", "compiled",
        "flops",
    )

    def __init__(self, fingerprint, epoch, step, params, jitted):
        self.fingerprint = fingerprint
        self.epoch = epoch
        self.step = step
        self.params = params
        self.jitted = jitted
        self.compiled = {}  # shape key -> AOT-compiled executable
        self.flops = {}  # shape key -> XLA-reported FLOPs per call (or None)

    def _shape_key(self, x):
        if isinstance(x, tuple):
            return tuple((a.shape, str(a.dtype)) for a in x)
        return ((x.shape, str(x.dtype)),)

    # with dynamic batching ON the shape set is exactly the bucket ladder;
    # OFF dispatches raw request shapes — bound the cache so an adversarial
    # shape stream cannot grow it without limit (PR 6's executor program
    # cache makes the same call, LRU 32)
    MAX_COMPILED = 32

    def compiled_for(self, x):
        """The AOT executable for this batch's exact shapes, compiling on
        miss. Lock-free: two threads racing the same miss both compile and
        one wins the dict slot — wasteful once, never wrong."""
        key = self._shape_key(x)
        fn = self.compiled.get(key)
        if fn is None:
            import jax

            from raydp_tpu import obs

            def sds(a):
                return jax.ShapeDtypeStruct(a.shape, a.dtype)

            with obs.span("serve.replica_compile", bucket=int(len(f0(x)))):
                fn = self.jitted.lower(
                    jax.tree.map(sds, self.params), fmap(sds, x)
                ).compile()
            obs.metrics.counter("serve.replica.compiles").inc()
            # FLOP-account the new executable HERE, inside the one-time
            # compile path (boot warm / first-touch): cost_analysis() is
            # not free, and charging it to the first REQUEST per bucket
            # puts a one-off spike straight into that request's latency —
            # at bench request counts those few spikes ARE the p99
            from raydp_tpu.obs.costmodel import step_flops_from_compiled

            self.flops[key] = step_flops_from_compiled(fn)
            while len(self.compiled) >= self.MAX_COMPILED:
                try:
                    evicted = next(iter(self.compiled))
                    self.compiled.pop(evicted, None)
                    self.flops.pop(evicted, None)
                except (StopIteration, RuntimeError):  # raydp-lint: disable=swallowed-exceptions (a racing evictor emptied/mutated the dict first; the cache is already under its bound)
                    break
            self.compiled[key] = fn
        return fn

    def flops_for(self, x):
        """XLA's per-call FLOP count for this batch shape (None when the
        backend doesn't report, or before the shape's compile recorded
        it) — the numerator of the live serve.mfu gauge. A pure cache
        read: the request path must never pay the analysis."""
        return self.flops.get(self._shape_key(x))


_PEAK_FLOPS = None


def _device_peak():
    """Cached peak FLOP/s of this replica's device (obs/costmodel.py table;
    None when unknown — the mfu gauge then simply never moves)."""
    global _PEAK_FLOPS
    if _PEAK_FLOPS is None:
        from raydp_tpu.obs.costmodel import device_peak_flops

        _PEAK_FLOPS = device_peak_flops()
    return _PEAK_FLOPS.get("peak")


class ModelReplica:
    """The actor class. Spawned with ``max_concurrency >= 2`` so ``reload``
    (and health probes) proceed while ``infer`` traffic is in flight."""

    def __init__(self, spec: ReplicaSpec):
        self._spec = spec
        self._active: Optional[_ModelState] = None
        # serializes reloads only — infer never takes it (infer reads the
        # _active reference, which swaps atomically)
        from raydp_tpu import sanitize

        self._reload_lock = sanitize.named_lock(
            "serve.replica_reload", threading.Lock()
        )
        # lazy decode engine (serve/decode.py): built on the first
        # decode_submit so non-streaming deployments pay nothing
        self._decode = None
        self._decode_lock = sanitize.named_lock(
            "serve.replica_decode", threading.Lock()
        )
        import jax

        from raydp_tpu.compile_cache import enable_compile_cache
        from raydp_tpu.estimator.jax_estimator import JaxEstimator

        devices = jax.devices()
        self._device = {
            "platform": devices[0].platform,
            "device_kind": devices[0].device_kind,
            "device_count": len(devices),
        }
        if spec.platform and self._device["platform"] != spec.platform:
            raise RuntimeError(
                f"replica {spec.name!r} came up on "
                f"{self._device['platform']!r} ({self._device['device_kind']}), "
                f"not the requested {spec.platform!r} — is the chip held by "
                "another process?"
            )
        enable_compile_cache()
        self._est = JaxEstimator(
            model=spec.model,
            checkpoint_dir=spec.checkpoint_dir,
            **dict(spec.extra_estimator_kwargs),
        )
        self._load()  # a replica is never "up but weightless"

    # -- lifecycle -----------------------------------------------------

    def _load(self) -> dict:
        """Restore the newest committed checkpoint and build a fresh
        generation, warming the configured buckets BEFORE the swap: until
        the new state is compiled, ``self._active`` (the old weights) keeps
        serving — the rolling-reload contract."""
        import jax

        from raydp_tpu import obs

        with self._reload_lock:
            epoch, step = self._est.load_latest_checkpoint()
            fingerprint = hashlib.blake2b(
                f"{self._spec.checkpoint_dir}:{epoch}:{step}".encode(),
                digest_size=8,
            ).hexdigest()
            state = _ModelState(
                fingerprint, epoch, step, self._est._params,
                jax.jit(self._est._module.apply),
            )
            if self._spec.example is not None:
                from raydp_tpu.exchange.features import (
                    as_feature_rows,
                    pad_rows,
                )

                rows = as_feature_rows(self._spec.example)
                for bucket in self._spec.buckets:
                    if int(bucket) >= len(f0(rows)):
                        state.compiled_for(pad_rows(rows, int(bucket)))
            self._active = state  # the atomic swap: new weights go live here
            # a live decode engine holds the OLD params captured in its
            # jits — retire it; the next decode_submit rebuilds against
            # the new generation (in-flight streams fail-fast and the
            # client re-prefills, same as a replica death)
            with self._decode_lock:
                stale, self._decode = self._decode, None
            if stale is not None:
                stale.close()
            obs.metrics.counter("serve.replica.reloads").inc()
            obs.flush_throttled()
            return self.info()

    def infer(self, x, n_valid: int):
        """Run the batch through the active generation and return
        ``(rows, compute_s)``: the FIRST ``n_valid`` prediction rows as host
        numpy — padded rows are sliced off server-side, so they cannot leak
        into any response — plus the measured compute seconds (the batcher's
        per-stage latency decomposition and the dispatch-vs-compute split in
        request traces both read it). The ``serve.replica_infer`` span
        parents under the dispatching batch's trace context, which rode in
        on the RPC frame — the replica-side hop of a sampled request
        trace."""
        import time as _time

        from raydp_tpu import obs

        state = self._active
        with obs.span(
            "serve.replica_infer", rows=int(n_valid),
            fingerprint=state.fingerprint,
        ):
            fn = state.compiled_for(x)
            t0 = _time.perf_counter()
            out = np.asarray(fn(state.params, x))[: int(n_valid)]
            compute_s = _time.perf_counter() - t0
        obs.metrics.counter("serve.replica.infers").inc()
        obs.metrics.counter("serve.replica.rows").inc(int(n_valid))
        obs.metrics.histogram("serve.replica.compute_s").observe(compute_s)
        # live serving MFU: XLA-reported FLOPs of this exact compiled shape
        # over measured compute, against the device's table peak — the
        # serving-plane twin of the estimator's fit-loop mfu gauge
        flops = state.flops_for(x)
        peak = _device_peak()
        if flops and peak and compute_s > 0:
            obs.metrics.gauge("serve.mfu").set(flops / compute_s / peak)
        obs.flush_throttled()
        return out, compute_s

    def reload(self) -> dict:
        """Pick up the newest checkpoint (rolling reload entry point). Old
        weights serve until the new generation is restored and warm."""
        return self._load()

    # -- decode serving (docs/serving.md, "Decode serving") ------------

    def _decode_engine(self):
        engine = self._decode
        if engine is not None:
            return engine
        with self._decode_lock:
            if self._decode is None:
                from raydp_tpu.serve.decode import DecodeEngine

                state = self._active
                self._decode = DecodeEngine(
                    self._est._module, state.params,
                    **dict(self._spec.decode or {}),
                )
            return self._decode

    def decode_submit(
        self, prompt_tokens, max_new_tokens: int, stream_id=None,
        trace_ctx=None,
    ) -> str:
        """Queue an autoregressive generation on this replica's
        continuous-batching engine; returns the stream id to poll.
        ``trace_ctx`` is a sampled stream's (trace_id, root_span_id) —
        the engine's prefill + step fan-in spans parent under it, the
        replica-side hop of one stream trace."""
        return self._decode_engine().submit(
            prompt_tokens, max_new_tokens, stream_id, trace_ctx=trace_ctx
        )

    def decode_poll(self, stream_id: str, cursor: int = 0) -> dict:
        """Tokens at/after ``cursor`` plus terminal state for a stream."""
        return self._decode_engine().poll(stream_id, cursor)

    def decode_stats(self) -> dict:
        engine = self._decode
        return engine.stats() if engine is not None else {}

    def decode_explain(self, stream_id=None):
        """The engine-kept timing record for one retired stream (newest by
        default) — fetched by ``deployment.explain_last_stream()``; works
        with tracing off. None when the engine never ran or the record
        aged out."""
        engine = self._decode
        return engine.explain(stream_id) if engine is not None else None

    def warm(self, example) -> int:
        """Precompile every configured bucket for ``example``'s row shape;
        returns the number of compiled entries in the active generation."""
        from raydp_tpu.exchange.features import as_feature_rows, pad_rows

        state = self._active
        rows = as_feature_rows(example)
        for bucket in self._spec.buckets:
            if int(bucket) >= len(f0(rows)):
                state.compiled_for(pad_rows(rows, int(bucket)))
        return len(state.compiled)

    def profile(self, payload=None, out_dir: Optional[str] = None) -> dict:
        """On-demand compute capture of ONE warm inference (the serve
        plane's half of the compute observatory, obs/profiler.py): run
        ``payload`` (default: the deployment's warm ``example``) through
        the active generation under a capture window — ``jax.profiler``
        deep trace when the backend supports it, span-only otherwise —
        and return the capture summary + measured compute. The capture
        runs in THIS replica process; artifacts land in its ``artifacts/``
        dir (``RAYDP_TPU_ARTIFACTS_DIR`` routes them)."""
        import time as _time

        from raydp_tpu.exchange.features import as_feature_rows, pad_rows
        from raydp_tpu.obs.profiler import capture

        source = payload if payload is not None else self._spec.example
        if source is None:
            raise ValueError(
                "profile() needs a payload (deployment has no example=)"
            )
        from raydp_tpu import obs

        from raydp_tpu.exchange.features import f_slice

        rows = as_feature_rows(source)
        # route through the batcher's bucket shapes (pad to the smallest
        # fitting bucket; an oversized payload is TRUNCATED to the largest
        # — the serving path only ever runs bucket shapes, and a raw shape
        # must not compile into the bucket-keyed cache of a live replica)
        # and warm OUTSIDE the window: the capture must show one
        # steady-state inference, not an XLA compile
        n_rows = len(f0(rows))
        if self._spec.buckets:
            fitting = [
                int(b) for b in self._spec.buckets if int(b) >= n_rows
            ]
            if fitting:
                rows = pad_rows(rows, min(fitting))
            else:
                largest = max(int(b) for b in self._spec.buckets)
                rows = f_slice(rows, 0, largest)
                n_rows = largest
        state = self._active
        fn = state.compiled_for(rows)
        np.asarray(fn(state.params, rows))  # uncaptured warm-up call
        with capture(out_dir=out_dir) as cap:
            # a real span inside the window: the span-only fallback arm
            # captures at least the inference interval it exists to show
            with obs.span("serve.replica_profile",
                          fingerprint=state.fingerprint):
                t0 = _time.perf_counter()
                np.asarray(fn(state.params, rows))
                compute_s = _time.perf_counter() - t0
        result = cap.result()
        result.update({
            "compute_ms": round(compute_s * 1000.0, 3),
            "rows": n_rows,
            "batch_rows": len(f0(rows)),  # the bucket shape actually run
            "fingerprint": state.fingerprint,
        })
        return result

    def info(self) -> dict:
        import os

        state = self._active
        return {
            "name": self._spec.name,
            "pid": os.getpid(),
            "fingerprint": state.fingerprint if state else None,
            "epoch": state.epoch if state else None,
            "step": state.step if state else None,
            "buckets_compiled": len(state.compiled) if state else 0,
            **self._device,
        }
