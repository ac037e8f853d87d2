"""``model.attention_scope_ms``: device milliseconds a training step spends
under the device scope ``hybridlm.attention`` (a model with window layers
opens ``.window`` or ``.global`` inside it): the q, k, v and o products,
RoPE, the repeat of K and V to the query heads and the flash kernels,
forward, recomputed and backward, each operation counted once, the traced
stretch's evaluations included, over the steps the program counted as
completed there (``values["steps_in_trace"]``). Membership as the PROGRAM
gives it (``harness/scopes.py``: the trace's operations joined to
``obs.profiler.device_scopes()`` by instruction name). Not in it: the norm
before the mixer, and the update of the layer's matrices where XLA names the
fusion by the optimizer. None without a trace, a count or a program that
gives the map."""

from benchmark.harness import scopes


def read(sources):
    if not sources.get("values", {}).get("steps_in_trace"):
        return None
    return scopes.member_ms_per_step(sources, "hybridlm.attention")
