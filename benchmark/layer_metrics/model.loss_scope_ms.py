"""``model.loss_scope_ms``: device milliseconds a training step spends under
the model's loss scope (``looplm.exit_loss`` or ``hybridlm.loss``: the head's
product, the cross-entropy and their gradients, the product back to the
hidden state among them, chunk by chunk), the traced stretch's evaluations
included, over the steps the program counted as completed there
(``values["steps_in_trace"]``). Membership as the PROGRAM gives it
(``harness/scopes.py``: the trace's operations joined to
``obs.profiler.device_scopes()`` by instruction name). ``model.exit_loss_ms``
sees only operations whose result carries the vocabulary axis, and reads
lower. Not in it: the head's update (``optimizer_update``). None without a
trace, a count or a program that gives the map."""

from benchmark.harness import scopes


def read(sources):
    if not sources.get("values", {}).get("steps_in_trace"):
        return None
    return scopes.member_ms_per_step(
        sources, "looplm.exit_loss", "hybridlm.loss")
