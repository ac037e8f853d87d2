"""``model.mtp_scope_ms``: device milliseconds a training step spends under
the device scope ``hybridlm.mtp``: the whole multi-token-prediction module:
its combine (two norms, the gather of the next tokens' embeddings,
``eh_proj``: ``hybridlm.mtp.combine``), its block (latent attention over the
experts, under the scopes every block has, so this time is ALSO in
``model.attention_scope_ms`` and ``model.experts_scope_ms``) and its loss
(the shared head's second pass, ``hybridlm.mtp.loss``: NOT in
``model.loss_scope_ms``, which stays the main head's); forward, recomputed
and backward, the traced stretch's evaluations included, over the steps the
program counted as completed there (``values["steps_in_trace"]``).
Membership as the PROGRAM gives it (``harness/scopes.py``). Not in it: the
update of the module's leaves (``optimizer_update``). None without a trace,
a count, a program that gives the map, or a program that has no such scope
(one from before the module, or a model without one)."""

from benchmark.harness import scopes


def read(sources):
    if not sources.get("values", {}).get("steps_in_trace"):
        return None
    return scopes.member_ms_per_step(sources, "hybridlm.mtp")
