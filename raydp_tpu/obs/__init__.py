"""Cluster-wide observability: tracing, metrics, structured logging, export.

One instrumentation plane for the whole runtime (SURVEY §5: the reference
defers everything to the Ray/Spark dashboards; we own the runtime, so we own
the telemetry). Three pieces:

- **Tracing** (`obs.span` / `obs.instant`): lightweight spans buffered in a
  per-process ring buffer and shipped to the head, with trace/span ids
  propagated inside control-plane RPC frames so one query or one ``fit()``
  yields a single causally-linked trace across driver, head, agents and
  executors. Disabled by default (``RAYDP_TPU_TRACE=1`` enables shipping);
  the disabled fast path is one branch per span.
- **Metrics** (`obs.metrics`): an always-on process-local registry of
  counters/gauges/histograms (RPC latency, store bytes, dispatch batches,
  task retries, streaming idle, estimator step/compile time), pushed to the
  head with each trace flush and queryable via ``cluster.dump_metrics()``.
- **Export** (`obs.export_trace`): writes Chrome-trace/Perfetto JSON — one
  track per process/actor, spans plus instant events for retries/restarts/
  fusion decisions. ``last_query_stats`` and estimator timings are derived
  from the SAME spans, not parallel hand-rolled timers.

This module is import-light by design (stdlib only): it is imported by the
zygote and by ``python -S`` worker processes.
"""

from __future__ import annotations

from raydp_tpu.obs.logging import get_logger, log
from raydp_tpu.obs.metrics import metrics
from raydp_tpu.obs.tracing import (
    collect,
    current_context,
    current_sinks,
    enabled,
    flush,
    flush_throttled,
    instant,
    mint_context,
    record_span,
    set_process_role,
    span,
    use_context,
    use_sinks,
    with_context,
)

__all__ = [
    "collect",
    "current_context",
    "current_sinks",
    "device_scope",
    "enabled",
    "explain_last_query",
    "export_trace",
    "flush",
    "flush_throttled",
    "get_logger",
    "instant",
    "log",
    "metrics",
    "mint_context",
    "profile_fit",
    "query_local_series",
    "record_span",
    "sample_memory",
    "set_process_role",
    "span",
    "use_context",
    "use_sinks",
    "with_context",
]


def export_trace(path: str) -> str:
    """Write the collected cluster trace as Chrome-trace/Perfetto JSON.
    Lazy import: export touches the cluster API, which span/metric call
    sites inside the cluster layer itself must never pull in at import."""
    from raydp_tpu.obs.export import export_trace as _export

    return _export(path)


def dump_metrics() -> dict:
    from raydp_tpu.obs.export import dump_metrics as _dump

    return _dump()


def explain_last_query(session=None, top_k: int = 5) -> dict:
    """Critical-path wall-time attribution of the active session's last
    query (obs/analysis.py). Lazy import: the analyzer touches the session
    layer, which obs call sites inside it must never pull in at import."""
    from raydp_tpu.obs.analysis import explain_last_query as _explain

    return _explain(session=session, top_k=top_k)


def query_local_series(name: str, window_s: float = 60.0, labels=None):
    """This process's windowed time-series mirror (obs/timeseries.py) —
    what in-process controllers read; ``cluster.query_metrics`` is the
    cluster-wide (head TSDB) flavor."""
    from raydp_tpu.obs.timeseries import query_local

    return query_local(name, window_s, labels)


def profile_fit(steps: int = 16, out_dir=None, jax_trace: bool = True):
    """Arm a bounded fit capture window (obs/profiler.py): the jax deep
    trace covers the first ``steps`` train steps, the span capture the
    whole ``with`` body. Lazy import: the profiler touches jax on demand."""
    from raydp_tpu.obs.profiler import profile_fit as _profile_fit

    return _profile_fit(steps=steps, out_dir=out_dir, jax_trace=jax_trace)


def device_scope(name: str):
    """Name a region of a compiled program (obs/profiler.py): a
    ``jax.named_scope`` whose name the profiler's scope map knows. Lazy
    import: jax loads when the first scope is opened, under a trace."""
    from raydp_tpu.obs.profiler import device_scope as _device_scope

    return _device_scope(name)


def sample_memory(force: bool = False):
    """Sample this process's memory watermark plane now (obs/profiler.py);
    normally rides every telemetry flush tick automatically."""
    from raydp_tpu.obs.profiler import sample_memory as _sample

    return _sample(force=force)
