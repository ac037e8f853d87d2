"""``model.ssd_scope_ms``: device milliseconds a training step spends under
the device scope ``ssd`` (``ops/ssd.py``: the chunked state-space scan from
the decays to ``y``; forward, recomputed and backward; not the projections,
the convolution or the gated norm), over the steps the program counted as
completed in the traced stretch (``values["steps_in_trace"]``). Membership as
the PROGRAM gives it (``harness/scopes.py``: the trace's operations joined to
``obs.profiler.device_scopes()`` by instruction name), so a scan written as
one kernel is found as the scan in XLA's operations is: ``model.ssd_ms``
finds them by the shapes of the present implementation. In it: the scan's
last elementwise pass, which ``model.ssd_ms`` misses. Not in it: the layout
copies and the cumulative sums' reduce-windows the compiler makes for the
scan, which carry no ``op_name`` (``device.scope_unattributed_share`` holds
them), and the copies of ``x`` and ``y`` at the scope's edge, named by
``hybridlm.mamba``'s reshapes. None without a trace, a count or a program that
gives the map."""

from benchmark.harness import scopes


def read(sources):
    if not sources.get("values", {}).get("steps_in_trace"):
        return None
    return scopes.member_ms_per_step(sources, "ssd")
