"""Analytic compute cost model: FLOPs accounting, device peaks, MFU.

The FLOPs accounting the cluster carries, so that MFU exists while any fit
or decode runs:

- **analytic FLOPs** for the model families the repo ships
  (:func:`lm_train_flops_per_step`, :func:`mlp_train_flops_per_step`) —
  matmul-only accounting, fwd+bwd as 3x forward;
- **measured FLOPs** from XLA's own cost analysis
  (:func:`step_flops_from_compiled`) — what the estimator's live MFU gauge
  uses, since a fit's step function is arbitrary user code the analytic
  tables can't know. The two accountings agree to within the optimizer /
  elementwise overhead XLA counts and the analytic tables deliberately
  ignore (``tests/test_profiler.py`` cross-checks them;
  docs/observability.md "Compute observatory");
- **peak FLOP/s** per device (:func:`device_peak_flops`): the TPU bf16
  table, an env override (``RAYDP_TPU_PEAK_FLOPS``) for exotic backends,
  and a NOMINAL cpu estimate (cores × 3 GHz × 16 f32 lanes) so the MFU
  gauge exists on dev boxes too — explicitly approximate, labeled
  ``peak_source`` so nobody mistakes a CPU MFU for a measured roofline.

Stdlib + jax-on-demand only: importable before (or without) jax.
"""

from __future__ import annotations

import os
from typing import Any, Optional, Sequence, Tuple

PEAK_FLOPS_ENV = "RAYDP_TPU_PEAK_FLOPS"

# bf16 peak FLOP/s per jax device, matched by substring of device_kind.
# v2/v3 expose one device per CORE (half a chip); v4+ one per chip.
TPU_PEAK_FLOPS: Tuple[Tuple[str, float], ...] = (
    ("v6", 918e12),  # Trillium / v6e
    ("v5p", 459e12),
    ("v5", 197e12),  # v5e / "v5 lite"
    ("v4", 275e12),
    ("v3", 61.5e12),
    ("v2", 22.5e12),
)

# nominal per-core CPU f32 peak: 3 GHz × (8-wide FMA = 16 flops/cycle).
# Deliberately crude — the point of a CPU MFU is trend lines on dev boxes,
# not a roofline claim (peak_source says "nominal-cpu").
_CPU_NOMINAL_PER_CORE = 3.0e9 * 16


def device_peak_flops(device: Any = None) -> dict:
    """``{kind, peak, peak_source}`` for ``device`` (default: the first
    jax device). ``peak_source`` is one of ``tpu-table`` / ``env`` /
    ``nominal-cpu`` / ``unknown`` (``peak`` None). A TPU whose kind the
    table does not know is an error, not ``unknown``: an MFU gauge that
    silently never moves on the chip it exists for hides the device."""
    override = os.environ.get(PEAK_FLOPS_ENV)
    if device is None:
        import jax

        device = jax.devices()[0]
    kind = getattr(device, "device_kind", str(device))
    if override:
        return {"kind": kind, "peak": float(override), "peak_source": "env"}
    low = kind.lower()
    for sub, peak in TPU_PEAK_FLOPS:
        if sub in low:
            return {"kind": kind, "peak": peak, "peak_source": "tpu-table"}
    if "cpu" in low:
        cores = os.cpu_count() or 1
        return {
            "kind": kind,
            "peak": cores * _CPU_NOMINAL_PER_CORE,
            "peak_source": "nominal-cpu",
        }
    if getattr(device, "platform", None) == "tpu":
        raise ValueError(
            f"TPU device kind {kind!r} is not in TPU_PEAK_FLOPS; add its "
            f"peak to the table or set {PEAK_FLOPS_ENV}"
        )
    return {"kind": kind, "peak": None, "peak_source": "unknown"}


# ---------------------------------------------------------------------------
# analytic FLOPs (matmul-only; train = 3x forward — the BENCH convention)
# ---------------------------------------------------------------------------


def lm_train_flops_per_step(batch: int, seq: int, d_model: int,
                            num_layers: int, vocab: int) -> int:
    """Analytic matmul FLOPs of one TransformerLM training step (fwd+bwd,
    no remat): per token per layer 24*d^2 (qkv 6d^2, proj 2d^2, mlp 16d^2)
    plus causal attention 2*d*(T+1) (QK^T + AV at average context (T+1)/2),
    plus the d*V lm_head; backward costs 2x forward."""
    per_token = num_layers * (24 * d_model**2 + 2 * d_model * (seq + 1))
    per_token += 2 * d_model * vocab
    return 3 * batch * seq * per_token


def mlp_train_flops_per_step(batch: int, layer_dims: Sequence[int]) -> int:
    """Analytic matmul FLOPs of one dense-MLP training step: forward is
    2*B*d_in*d_out per layer, backward costs 2x forward (grad wrt inputs
    AND weights) — bias adds / activations / optimizer elementwise work
    excluded by convention, exactly like the LM accounting."""
    dims = list(layer_dims)
    fwd = sum(2 * batch * a * b for a, b in zip(dims[:-1], dims[1:]))
    return 3 * fwd


# ---------------------------------------------------------------------------
# measured FLOPs: XLA cost analysis of a lowered/compiled step
# ---------------------------------------------------------------------------


def step_flops_from_compiled(compiled: Any) -> Optional[float]:
    """Total FLOPs XLA attributes to one execution of ``compiled`` (an AOT
    ``jax.stages.Compiled`` or anything exposing ``cost_analysis()``).
    Returns None when the backend doesn't report — callers must treat
    this as "unknown", never zero."""
    try:
        cost = compiled.cost_analysis()
    except Exception:  # raydp-lint: disable=swallowed-exceptions (cost analysis is backend-optional; unknown is a valid answer)
        return None
    # jax has returned both a dict and a 1-element list of dicts over time
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else None
    if not isinstance(cost, dict):
        return None
    flops = cost.get("flops")
    if flops is None or flops <= 0:
        return None
    return float(flops)


def step_flops_abstract(fn: Any, *args) -> Optional[float]:
    """FLOPs of one call of ``fn`` at ``args``'s shapes — args may be
    ``jax.ShapeDtypeStruct`` pytrees (nothing is materialized). Used by the
    segment-scanned fit paths: XLA's cost analysis counts a ``lax.scan``
    BODY once regardless of trip count, so the compiled segment's number
    cannot be divided by steps — the single-step function is lowered
    abstractly instead (one bounded extra compile per fit, served by the
    persistent compilation cache on repeats)."""
    import jax

    try:
        return step_flops_from_compiled(jax.jit(fn).lower(*args).compile())
    except Exception:  # raydp-lint: disable=swallowed-exceptions (an unloweable step degrades to an unknown flops count, not a failed fit)
        return None


def mfu(model_flops_per_sec: Optional[float],
        peak_flops: Optional[float]) -> Optional[float]:
    """Model FLOPs utilization; None when either side is unknown."""
    if not model_flops_per_sec or not peak_flops:
        return None
    return model_flops_per_sec / peak_flops


# ---------------------------------------------------------------------------
# cross-host placement (ISSUE 18)
# ---------------------------------------------------------------------------

WIRE_GBPS_ENV = "RAYDP_TPU_WIRE_GBPS"
# nominal host-to-host wire bandwidth: 10 Gb/s ≈ 1.25 GB/s. Deliberately a
# planning constant, not a measurement — placement scoring only needs the
# RELATIVE cost of moving each host's bytes, and the env override exists
# for clusters whose fabric is genuinely different.
_WIRE_BYTES_PER_S_DEFAULT = 1.25e9


def wire_bytes_per_s() -> float:
    try:
        gbps = float(os.environ.get(WIRE_GBPS_ENV, "") or 10.0)
    except ValueError:
        gbps = 10.0
    return gbps * 1e9 / 8.0


def exchange_placement(bytes_by_host: dict) -> Tuple[Optional[str], dict]:
    """Score reduce/exchange placement per candidate host: the estimated
    seconds of wire transfer if the task runs THERE (every byte not already
    on that host crosses the wire at the nominal bandwidth). Returns
    ``(best_host, {host: est_transfer_s})`` — best is the host holding the
    most input bytes, with deterministic (host-name) tie-breaking so two
    planners given the same map score the same placement. Empty input
    scores to ``(None, {})``."""
    if not bytes_by_host:
        return None, {}
    bw = wire_bytes_per_s()
    total = sum(bytes_by_host.values())
    scores = {
        host: (total - local) / bw for host, local in bytes_by_host.items()
    }
    best = min(scores, key=lambda h: (scores[h], str(h)))
    return best, scores
