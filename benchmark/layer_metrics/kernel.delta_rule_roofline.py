"""``kernel.delta_rule_roofline``: share of its roofline of the gated delta
rule: the least time the chip could take for the RECURRENCES of the steps
traced, whatever implements them (a forward and a backward pass a delta-rule
layer and step: ``harness/delta_costs.delta_fwd`` / ``delta_bwd`` against the
peaks table: 6 Dk Dv FLOPs a token and held head forward and twice that
backward; q, k, v, alpha, beta read and o written once, and in the backward
pass those and do read and the five gradients written once) over the summed
device time under the scope ``delta_rule`` (what ``model.delta_scope_ms``
reports: the forward recomputed in the backward pass and the stretch's
evaluations are in the divisor and not in the needed work, so the share reads
low and never high). By scope, not by shapes (``harness/scopes.py``). None
without a trace, a count of steps, a model that has the rule, or a program
that names no such scope."""

from benchmark.harness import costs, scopes


def read(sources):
    values, kernels = sources.get("values", {}), sources.get("kernels", {})
    steps = values.get("steps_in_trace")
    fwd, bwd = kernels.get("delta_fwd"), kernels.get("delta_bwd")
    if not steps or not fwd or not bwd:
        return None
    made = scopes.table(sources)
    seconds = made.member_s("delta_rule") if made is not None else 0.0
    if seconds <= 0:
        return None
    least = sum(costs.roofline(k["cost"], sources["peaks"])["min_s"]
                for k in (fwd, bwd))
    return 100.0 * steps * fwd["layers"] * least / seconds
