"""Span API: per-process ring buffer + trace-context propagation.

A span is a plain dict (picklable, cheap): ``{name, ts, dur, pid, tid, proc,
trace, id, parent, args}`` with ``ts``/``dur`` in microseconds of wall time
(``time.time_ns`` — one comparable timeline across processes on a machine;
multi-host traces carry each host's clock, see docs/observability.md).

In a process that has ALREADY imported jax, every real span also opens a
``jax.profiler.TraceAnnotation`` of its name: while a profiler session runs
the span lies in the trace's ``/host:`` plane, on the device trace's clock
(one inactive TraceMe otherwise). This module never imports jax itself — an
executor without jax pays one ``sys.modules`` lookup per real span.

Two consumers, decoupled:

- **collectors** (thread-local, always available): ``with collect() as got:``
  captures every span finished on this thread — the planner derives
  ``last_query_stats`` from these, so query stats work with tracing OFF.
- **the ring buffer** (process-global, gated on ``RAYDP_TPU_TRACE``):
  finished spans buffer here and ship to the head on ``flush()`` / atexit /
  buffer pressure. With tracing disabled and no collector installed,
  ``span()`` returns a shared no-op after ONE branch — the hot-path cost the
  ISSUE budget allows.

Context: ``(trace_id, span_id)`` pairs travel thread-locally; ``span()``
parents under the current context and installs itself for its body. RPC
clients attach the current context to outgoing frames (common.rpc /
ActorHandle) and servers adopt it around the handled call, so causality
crosses process boundaries without any span caring. Beside the pair a real
span that is entered leaves its NAME for its body (``current_span_name``):
it stays on the thread and rides no frame.
"""

from __future__ import annotations

import atexit
import collections
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Tuple

TRACE_ENV = "RAYDP_TPU_TRACE"
BUFFER_ENV = "RAYDP_TPU_TRACE_BUFFER"

_enabled = os.environ.get(TRACE_ENV, "0") not in ("", "0", "false", "False")
_buffer_cap = int(os.environ.get(BUFFER_ENV, "8192") or "8192")

from raydp_tpu.sanitize import named_lock as _named_lock

_tls = threading.local()
_buf_lock = _named_lock("obs._buf_lock")
_buffer: "collections.deque" = collections.deque(maxlen=_buffer_cap)
_dropped = 0  # spans evicted from the ring before a flush shipped them

# what this process calls itself in the trace (one Perfetto track per proc)
_role: str = "driver"


def set_process_role(role: str) -> None:
    """Label this process's track ("head" / "agent" / "worker:<actor-id>" /
    "zygote"); the driver default stands when nothing claims otherwise."""
    global _role
    _role = role


def process_role() -> str:
    # a worker process that never called set_process_role still labels
    # itself from its spawn environment
    global _role
    if _role == "driver":
        actor_id = os.environ.get("RAYDP_TPU_ACTOR_ID")
        if actor_id:
            _role = f"worker:{actor_id}"
    return _role


def enabled() -> bool:
    """Is trace shipping on? (Collectors work either way.)"""
    return _enabled


def set_enabled(value: bool) -> None:
    """Test/bench hook; prefer setting RAYDP_TPU_TRACE before process start
    so spawned actors inherit it."""
    global _enabled
    _enabled = bool(value)


def reinit_for_process(role: str) -> None:
    """Reset per-process tracing state after fork/exec into a new runtime
    role. Zygote-forked workers inherit the ZYGOTE's enablement and buffer;
    the session that requested the fork decides tracing (its env rode in
    with the fork request), so re-read the environment and start clean."""
    global _enabled, _dropped
    set_process_role(role)
    _enabled = os.environ.get(TRACE_ENV, "0") not in ("", "0", "false", "False")
    with _buf_lock:
        _buffer.clear()
    _dropped = 0


def _collectors() -> List[list]:
    got = getattr(_tls, "collectors", None)
    if got is None:
        got = _tls.collectors = []
    return got


def current_context() -> Optional[Tuple[str, str]]:
    """(trace_id, span_id) the next span parents under, or None."""
    return getattr(_tls, "ctx", None)


def _set_context(ctx: Optional[Tuple[str, str]]) -> None:
    _tls.ctx = ctx


def current_span_name() -> Optional[str]:
    """The name of the innermost real span open on this thread (entered
    with ``with``), or None: what a compile that no ``estimator.compile``
    span holds is listed under (obs/profiler.py, "compile account"). The
    shared no-op span sets nothing."""
    return getattr(_tls, "span_name", None)


class use_context:
    """Adopt a remote caller's (trace_id, span_id) for a code region — the
    server half of cross-process propagation."""

    def __init__(self, ctx: Optional[Tuple[str, str]]):
        self._ctx = tuple(ctx) if ctx else None
        self._saved: Optional[Tuple[str, str]] = None

    def __enter__(self):
        self._saved = current_context()
        if self._ctx is not None:
            _set_context(self._ctx)
        return self

    def __exit__(self, *exc):
        _set_context(self._saved)


def with_context(ctx, fn, *args, **kwargs):
    """Run ``fn`` under ``ctx`` — for handing the caller's trace context to
    worker-pool threads (thread-locals don't cross threads)."""
    with use_context(ctx):
        return fn(*args, **kwargs)


class _NoopSpan:
    """Shared do-nothing span for the disabled fast path."""

    __slots__ = ()
    duration = 0.0

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self

    def set_traced(self, **attrs):
        return self

    def start(self):
        return self

    def finish(self):
        pass


_NOOP = _NoopSpan()

# jax.profiler.TraceAnnotation, resolved the first time a span opens in a
# process where jax is loaded (the _device_live_bytes rule: obs never
# imports jax, and a process without it pays nothing)
_trace_annotation = None


def _open_annotation(name: str):
    """The span's twin in the profiler's trace, entered — or None where jax
    is not loaded (or only half imported on another thread)."""
    global _trace_annotation
    cls = _trace_annotation
    if cls is None:
        jax = sys.modules.get("jax")
        cls = getattr(getattr(jax, "profiler", None), "TraceAnnotation", None)
        if cls is None:
            return None
        _trace_annotation = cls
    annotation = cls(name)
    annotation.__enter__()
    return annotation


class Span:
    __slots__ = ("name", "args", "trace", "id", "parent", "_t0", "_ts",
                 "duration", "_saved_ctx", "_saved_name", "_ship", "_nested",
                 "_annotation")

    def __init__(self, name: str, args: Dict[str, Any], ship: bool):
        self.name = name
        self.args = args
        ctx = current_context()
        if ctx is None:
            self.trace = uuid.uuid4().hex[:16]
            self.parent = None
        else:
            self.trace, self.parent = ctx
        self.id = uuid.uuid4().hex[:16]
        self._ship = ship
        self._saved_ctx = ctx
        self._saved_name = None
        self._nested = False
        self._annotation = None
        self.duration = 0.0
        self._ts = time.time_ns() // 1000
        self._t0 = time.perf_counter()

    def set(self, **attrs) -> "Span":
        self.args.update(attrs)
        return self

    def set_traced(self, **attrs) -> "Span":
        """``set``, and the same attributes on the span's twin in the
        profiler's trace where it has one (numbers and strings): for what
        is known only at the span's end and belongs on the timeline."""
        self.args.update(attrs)
        if self._annotation is not None:
            self._annotation.set_metadata(**attrs)
        return self

    def elapsed(self) -> float:
        """Seconds since the span was created, while it is open."""
        return time.perf_counter() - self._t0

    def __enter__(self) -> "Span":
        _set_context((self.trace, self.id))
        self._saved_name = getattr(_tls, "span_name", None)
        _tls.span_name = self.name
        self._nested = True
        self._annotation = _open_annotation(self.name)
        return self

    def start(self) -> "Span":
        """Open WITHOUT becoming the context later spans parent under, for
        an interval that straddles other spans' boundaries (the fit's
        epoch restart runs from one epoch's fence into the next epoch's
        span); ``finish()`` closes it, on the same thread."""
        self._annotation = _open_annotation(self.name)
        return self

    def finish(self) -> None:
        self.__exit__(None, None, None)

    def __exit__(self, exc_type, exc, tb):
        self.duration = time.perf_counter() - self._t0
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if self._nested:
            _set_context(self._saved_ctx)
            _tls.span_name = self._saved_name
        if exc_type is not None:
            self.args.setdefault("error", exc_type.__name__)
        record = {
            "name": self.name,
            "ts": self._ts,
            "dur": int(self.duration * 1e6),
            "pid": os.getpid(),
            "tid": threading.get_ident() % 1_000_000,
            "proc": process_role(),
            "trace": self.trace,
            "id": self.id,
            "parent": self.parent,
            "args": self.args,
        }
        for sink in _collectors():
            sink.append(record)
        if self._ship:
            _buffer_append(record)
        return False


def span(name: str, **attrs):
    """Start a span. Context-manager; ``with obs.span("etl.stage", n=4) as s``.
    Disabled + no collector → shared no-op (one branch)."""
    if not _enabled and not getattr(_tls, "collectors", None):
        return _NOOP
    return Span(name, attrs, _enabled)


def instant(name: str, **attrs) -> None:
    """A zero-duration marker event (task retry, actor restart, fusion
    decision). Same gating as span()."""
    if not _enabled and not getattr(_tls, "collectors", None):
        return
    record = {
        "name": name,
        "ts": time.time_ns() // 1000,
        "dur": 0,
        "ph": "i",
        "pid": os.getpid(),
        "tid": threading.get_ident() % 1_000_000,
        "proc": process_role(),
        "trace": (current_context() or (uuid.uuid4().hex[:16],))[0],
        "id": uuid.uuid4().hex[:16],
        "parent": (current_context() or (None, None))[1],
        "args": attrs,
    }
    for sink in _collectors():
        sink.append(record)
    if _enabled:
        _buffer_append(record)


def record_span(
    name: str,
    ts_us: int,
    dur_us: int,
    trace: str,
    span_id: Optional[str] = None,
    parent: Optional[str] = None,
    **attrs,
) -> dict:
    """Emit a span RECORD for an interval measured elsewhere — the serving
    plane's request-path spans are assembled from per-request timestamps
    AFTER the request resolves (a live ``span()`` context manager cannot
    straddle the admission queue, the batch, and the dispatcher thread).
    Same consumers as ``Span.__exit__``: collectors and, when shipping is
    on, the ring buffer. Returns the record (its ``id`` links children)."""
    record = {
        "name": name,
        "ts": int(ts_us),
        "dur": max(0, int(dur_us)),
        "pid": os.getpid(),
        "tid": threading.get_ident() % 1_000_000,
        "proc": process_role(),
        "trace": trace,
        "id": span_id or uuid.uuid4().hex[:16],
        "parent": parent,
        "args": attrs,
    }
    for sink in _collectors():
        sink.append(record)
    if _enabled:
        _buffer_append(record)
    return record


def mint_context() -> Tuple[str, str]:
    """A fresh (trace_id, span_id) pair for a root minted out-of-band (the
    serve request path samples requests at admission and emits their spans
    at resolution via ``record_span``)."""
    return uuid.uuid4().hex[:16], uuid.uuid4().hex[:16]


def current_sinks() -> List[list]:
    """This thread's active collector sinks — capture them before handing
    work to a helper thread, and re-install there with ``use_sinks`` so the
    helper's spans still land in the same query's stats."""
    return list(_collectors())


class use_sinks:
    """Adopt another thread's collector sinks for a code region (the
    collector half of cross-THREAD propagation; ``use_context`` is the
    trace-id half). Appends are GIL-atomic, so two threads sharing a sink
    list interleave records without corruption."""

    def __init__(self, sinks: List[list]):
        self._sinks = list(sinks)

    def __enter__(self):
        _collectors().extend(self._sinks)
        return self

    def __exit__(self, *exc):
        got = _collectors()
        for sink in self._sinks:
            for i in range(len(got) - 1, -1, -1):
                if got[i] is sink:
                    del got[i]
                    break


class collect:
    """Capture every span/instant finished on THIS thread into a list —
    the local-stats consumer (planner query stats, task phase timing).
    Nesting composes: inner collectors see only their own region."""

    def __init__(self):
        self.records: List[dict] = []

    def __enter__(self) -> List[dict]:
        _collectors().append(self.records)
        return self.records

    def __exit__(self, *exc):
        # remove by IDENTITY: list.remove matches by equality and two empty
        # sink lists compare equal — nested collectors would detach each
        # other's sinks
        sinks = _collectors()
        for i in range(len(sinks) - 1, -1, -1):
            if sinks[i] is self.records:
                del sinks[i]
                break


_flush_inflight = threading.Event()


def _buffer_append(record: dict) -> None:
    global _dropped
    start_flush = False
    with _buf_lock:
        if len(_buffer) == _buffer.maxlen:
            _dropped += 1
        _buffer.append(record)
        if len(_buffer) >= (_buffer.maxlen or 1) // 2:
            # pressure flush on a background thread: a filling ring must not
            # stall the instrumented call site, nor silently drop — and at
            # most one flusher runs at a time
            start_flush = not _flush_inflight.is_set()
            if start_flush:
                _flush_inflight.set()
    if start_flush:
        threading.Thread(target=_pressure_flush, daemon=True).start()


def _pressure_flush() -> None:
    try:
        flush()
    finally:
        _flush_inflight.clear()


def drain_local() -> List[dict]:
    """Remove and return this process's buffered spans (flush/export path)."""
    with _buf_lock:
        out = list(_buffer)
        _buffer.clear()
    return out


def dropped_count() -> int:
    return _dropped


def flush() -> bool:
    """Ship buffered spans + the metrics snapshot to the head. Safe to call
    anywhere: no cluster, no session, or a dead head all degrade to keeping
    the spans local (they are re-buffered for the next attempt). The head
    process itself ingests directly — no RPC to self."""
    global _dropped
    from raydp_tpu.obs.metrics import metrics

    # memory watermark plane: every flush tick samples this process's
    # rss / shm-namespace / device bytes + pressure into the registry
    # FIRST, so the snapshot shipped below carries fresh mem.* gauges
    # (self-throttled to ~1s inside sample_memory; never raises)
    try:
        from raydp_tpu.obs.profiler import sample_memory

        sample_memory()
    except Exception:  # raydp-lint: disable=swallowed-exceptions (the memory sampler must never block a telemetry flush)
        pass

    spans = drain_local()
    snapshot = metrics.snapshot()
    if not spans and not snapshot:
        return True
    # the process-local time-series mirror rides the same tick: in-process
    # controllers (serve autoscaler, tenancy policies) get the identical
    # windowed signal a head scrape would show
    try:
        from raydp_tpu.obs import timeseries as _ts

        _ts.ingest_local(snapshot)
    except Exception:  # raydp-lint: disable=swallowed-exceptions (the local mirror must never block shipping to the head)
        pass
    # flight-recorder log ring: shipped alongside spans/metrics so the head
    # holds every process's recent log lines for crash dossiers
    from raydp_tpu.obs import recorder as _recorder

    logs = _recorder.drain_logs()
    proc = {"pid": os.getpid(), "role": process_role(), "dropped": _dropped}
    try:
        # the head's direct-ingest hook comes FIRST: the head process has
        # neither an initialized cluster API nor RAYDP_TPU_SESSION in its
        # env, so the cluster guard below would otherwise fail every head
        # flush and park head spans in the (smaller) process ring forever
        ingest = _local_ingest
        if ingest is not None:
            ingest(proc=proc, spans=spans, metrics_snapshot=snapshot,
                   logs=logs)
            return True
        from raydp_tpu.cluster import api as cluster_api

        if not cluster_api.is_initialized() and not os.environ.get(
            "RAYDP_TPU_SESSION"
        ):
            raise RuntimeError("no cluster")
        cluster_api.head_rpc(
            "obs_ingest", proc=proc, spans=spans,
            metrics_snapshot=snapshot, logs=logs, timeout=10.0,
        )
        return True
    except Exception:
        with _buf_lock:
            # re-buffer into the space left, preferring the NEWEST of the
            # failed batch (appendleft on a full deque would silently evict
            # from the right — i.e. drop spans recorded DURING the failed
            # flush); anything that doesn't fit is counted as dropped
            space = (_buffer.maxlen or 0) - len(_buffer)
            kept = spans[-space:] if space > 0 else []
            _dropped += len(spans) - len(kept)
            for record in reversed(kept):
                _buffer.appendleft(record)
        _recorder.requeue_logs(logs)
        return False


_last_flush = 0.0


def flush_throttled(min_interval: float = 0.5) -> None:
    """flush() at most every ``min_interval`` seconds — the per-dispatch
    ship point for processes that may be SIGKILLed (executors), cheap enough
    to call on every task. Runs with tracing OFF too: the metrics registry
    is always on, and its snapshots reach ``dump_metrics()`` this way."""
    global _last_flush
    now = time.monotonic()
    if now - _last_flush >= min_interval:
        _last_flush = now
        flush()


# set by the head process so its own spans skip the RPC loopback
_local_ingest = None


def set_local_ingest(fn) -> None:
    global _local_ingest
    _local_ingest = fn


@atexit.register
def _flush_at_exit() -> None:  # pragma: no cover - exit path
    if _enabled:
        try:
            flush()
        except Exception:  # raydp-lint: disable=swallowed-exceptions (atexit flush: the logging plane may already be gone)
            pass
