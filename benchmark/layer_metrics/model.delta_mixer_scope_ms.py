"""``model.delta_mixer_scope_ms``: device milliseconds a training step spends
under the device scope ``hybridlm.delta`` OUTSIDE ``delta_rule``: what a
delta-rule mixer (Olmo's ``delta``, Ling's ``kda``) does around its scan: the
q, k, v, gate, decay and beta projections, the depthwise convolution and its
silu, the l2 norms, the decay's own arithmetic, the read-out's norm and gate
and ``W_o``; forward, recomputed and backward, the traced stretch's
evaluations included, over the steps the program counted as completed there
(``values["steps_in_trace"]``). ``model.delta_scope_ms`` is the scan;
this is the rest of the mixer, counted from the operations whose chain holds
the one scope and not the other (``harness/scopes.py``), not by subtraction.
None without a trace, a count, a program that gives the map, or a program
that has no such scope."""

from benchmark.harness import scopes


def read(sources):
    made, count = scopes.table(sources), scopes.steps(sources)
    if made is None or not count or not sources.get("values", {}).get(
            "steps_in_trace"):
        return None
    ps = sum(op[2] for op in made.operations
             if "hybridlm.delta" in op[4] and "delta_rule" not in op[4])
    return 1e3 * ps / 1e12 / count if ps > 0 else None
