"""``model.shared_expert_scope_ms``: device milliseconds a training step
spends under the device scope ``hybridlm.experts.shared`` (the shared
expert's SwiGLU, which every token passes beside the routed experts: its two
products, the activation and the sum after the combine; forward, recomputed
and backward, the traced stretch's evaluations included), over the steps the
program counted as completed there (``values["steps_in_trace"]``). Inside
``model.experts_scope_ms``. Membership as the PROGRAM gives it
(``harness/scopes.py``). None without a trace, a count, a program that gives
the map, or a program that has no such scope."""

from benchmark.harness import scopes


def read(sources):
    if not sources.get("values", {}).get("steps_in_trace"):
        return None
    return scopes.member_ms_per_step(sources, "hybridlm.experts.shared")
