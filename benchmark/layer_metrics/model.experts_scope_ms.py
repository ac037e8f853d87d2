"""``model.experts_scope_ms``: device milliseconds a training step spends
under the device scope ``hybridlm.experts`` (``.route``, ``.dispatch``,
``.gmm``, ``.combine`` inside it, and the norm before the router where the
model opens the scope over it): every operation of the traced stretch whose
instruction the PROGRAM says belongs there, forward, recomputed and
backward, each counted once, whatever its shapes and whichever arm of the
layer's conditional ran (``harness/scopes.py``: the trace's operations joined
to ``obs.profiler.device_scopes()`` by instruction name; the conditionals'
containers left out), over the steps the program counted as completed there
(``values["steps_in_trace"]``). The stretch's evaluations run the layer's
forward too and are in the sum, as in ``model.moe_ms``, which finds the
operations by their shapes and counts the conditionals twice. Not in it: the
optimizer's update of the experts' weights (``optimizer_update``: their
gradients come out of Mosaic calls, so XLA fuses no update behind them).
None without a trace, a count or a program that gives the map."""

from benchmark.harness import scopes


def read(sources):
    if not sources.get("values", {}).get("steps_in_trace"):
        return None
    return scopes.member_ms_per_step(sources, "hybridlm.experts")
