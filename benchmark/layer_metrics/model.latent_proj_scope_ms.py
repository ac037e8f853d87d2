"""``model.latent_proj_scope_ms``: device milliseconds a training step spends
under the device scopes ``hybridlm.attention.query`` (a latent-attention
mixer's query: ``W_q``, or with a low-rank query ``W_qa``, its norm and
``W_qb``) and ``hybridlm.attention.latent`` (``W_kva``, the latent's norm and
``W_kvb``): what of ``model.attention_scope_ms`` is the mixer's PROJECTIONS
into the attention and not the flash pair (the rest of that scope is RoPE,
the one RoPE key's broadcast, the concatenations, the flash calls and
``W_o``); forward, recomputed and backward, every such layer and the
multi-token-prediction module's, the traced stretch's evaluations included,
over the steps the program counted as completed there
(``values["steps_in_trace"]``). The two scopes lie side by side, never one in
the other, so their membership sums add (``harness/scopes.py``). A program
that has the ``latent`` scope alone (one from before the ``query`` scope)
gives that part. None without a trace, a count, a program that gives the
map, or a program that has neither scope."""

from benchmark.harness import scopes


def read(sources):
    if not sources.get("values", {}).get("steps_in_trace"):
        return None
    return scopes.member_ms_per_step(
        sources, "hybridlm.attention.query", "hybridlm.attention.latent")
