"""Traffic kind ``lmpretrain_routed``: ``lmpretrain``'s job, phases, fence,
window, trace, result line and parts (b)-(d), for a language model WHOSE
ROUTING IS DISCRETE (routed experts: each token's top-k of the router's
scores). Only part (a)'s first half, the objective against the reference, is
this file's; everything else is ``lmpretrain``'s and ``lmfit``'s, unedited
(``lmpretrain.check_objective`` is the one name replaced, in this process
alone; a traced run also notes the program's counters at the two fences
where ``lmpretrain._fit`` starts and stops the profiler, ``run_phase``).

Why the comparison cannot be ``lmpretrain``'s: it runs the reference on its
own and takes the LARGEST logits gap over all tokens. A top-k is discrete.
bf16 operands move a router's logit by 2e-3; a token's k-th and (k+1)-th
scores lie closer than that for a few per cent of (token, layer) pairs, such
a token picks one other expert, and its logits are off by tens of per cent:
no limit on a maximum over 32,768 tokens passes that and still refuses a
wrong model. So the objective is judged in two parts:

(i) SELECTION. The model's ``loss(x, None, True)`` returns ``routing`` (int32
[expert layers, B, T, k]: what it chose). The reference is run UNDER THAT
ROUTING (``loss_and_grads(..., routing=ids)``: the experts applied are the
program's choice, the weights are from the reference's own scores at those
ids) and reports its own free top-k and, per token and layer, the margin
between its k-th and (k+1)-th biased score. Wherever the two choices differ
(as sets), the reference's margin there must be under ``margin_max``
(rounding can only flip a choice that was nearly a tie), and the share of
(token, layer) pairs that differ must be under ``differ_share_max``; for
``matched`` (float32 / highest) both limits are near zero.

(ii) ARITHMETIC. Loss, logits (max over tokens, relative to max
|reference|) and each parameter's gradient (L2, relative) against the
reference under the program's routing, ``as_run`` and ``matched``, at the
mix's limits: exactly ``lmpretrain``'s three gaps, with the routing taken out
of them. ``aux["pairs_dropped"]`` must be 0: a pair past the rows' buffer is
a wrong result.

``as_run`` and ``matched`` each run the program's own objective, so each has
its own routing and its own reference run. ``expert_bias`` is a parameter
like any other in both parts: in its gradient's place program and reference
hand back every expert's excess load (the balancing rule's input), which
under one routing is one count. The step (``step`` / ``step_own``) is
``lmpretrain``'s as it is: L2 changes over an epoch, which a handful of
flipped choices do not move; the reference routes freely there, and its
optimizer is given the warm-up and the rule's rate beside AdamW's four
numbers (``RoutedReference.epoch``).
``--check-seeds`` adds the second reading: the reference computed in bf16
from end to end, under the ``as_run`` routing, must be refused by one of the
arithmetic limits."""

from __future__ import annotations

import functools
import time
import types

import numpy as np

from benchmark.drivers import lmfit, lmpretrain
from benchmark.harness import moe_costs
from benchmark.drivers.lmpretrain import (  # noqa: F401 - a driver's surface
    GAPS, MODES, STEP_GAPS, _named, check_phases, phases)

SELECTION = ("differ_share", "margin_worst")


class RoutedReference(lmpretrain.Reference):
    """``lmpretrain.Reference`` for a model with routed experts: a batch's
    outputs one sequence at a time, under the routing handed over
    (``routing`` [expert layers, B, T, k]; None: the reference's own), and
    beside them the reference's free ``selection`` and ``margin``."""

    def _run(self, dtype):
        import jax

        key = ("routed reference", self.ref.__name__,
               repr(sorted(self.cfg.items())), self.block, str(dtype))
        if key not in lmfit._JITS:
            ref, cfg, block = self.ref, self.cfg, self.block
            lmfit._JITS[key] = jax.jit(
                lambda q, row, ids: ref.loss_and_grads(
                    q, row, cfg, block, True, dtype, with_states=True,
                    routing=ids))
            lmfit._JITS[key + ("free",)] = jax.jit(
                lambda q, row: ref.loss_and_grads(
                    q, row, cfg, block, True, dtype, with_states=True))
        return lmfit._JITS[key], lmfit._JITS[key + ("free",)]

    def __call__(self, p, rows, dtype, states=True, routing=None) -> dict:
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        forced, free = self._run(dtype)
        batch = rows.shape[0]
        loss, grads, hidden, selection, margin = 0.0, None, [], [], []
        for i in range(batch):
            value, aux, g = (
                free(p, rows[i:i + 1]) if routing is None
                else forced(p, rows[i:i + 1], routing[:, i:i + 1]))
            g = [np.asarray(a, np.float32) for a in jax.tree.leaves(g)]
            loss += float(value) / batch
            grads = g if grads is None else [a + b for a, b in zip(grads, g)]
            if states:
                hidden.append(aux["hidden"])
                selection.append(np.asarray(aux["selection"]))
                margin.append(np.asarray(aux["margin"]))
        if batch > 1:
            grads = [a / batch for a in grads]
        out = {"loss": loss, "grads": grads}
        if states:
            out.update(hidden=jnp.concatenate(hidden, axis=0),
                       selection=np.concatenate(selection, axis=1),
                       margin=np.concatenate(margin, axis=1))
        self.seconds += time.perf_counter() - t0
        return out

    def epoch(self, theta0, treedef, batches, hyper, loss_and_grads, first=None):
        """``lmpretrain``'s replay, which hands the reference's optimizer
        AdamW's four numbers: this model's optimizer has two more (the
        warm-up's steps, the balancing rule's rate) and is told which
        leaves are biases, bound for the replay."""
        import jax

        plain = self.ref
        self.ref = types.SimpleNamespace(**{
            **vars(plain), "adamw_step": functools.partial(
                plain.adamw_step,
                warmup_steps=hyper.get("warmup_steps", 0),
                expert_bias_rate=hyper.get("expert_bias_rate", 0.0),
                biases=plain.bias_leaves(
                    jax.tree.unflatten(treedef, theta0)))})
        try:
            return super().epoch(theta0, treedef, batches, hyper,
                                 loss_and_grads, first)
        finally:
            self.ref = plain


def selection_gaps(routing: np.ndarray, ref_out: dict) -> dict:
    """Where the program's choice [expert layers, B, T, k] and the
    reference's free one differ, as sets: the share of (token, layer) pairs
    that differ, the largest reference margin among them (0 where none
    does), and each layer's count."""
    differ = (np.sort(np.asarray(routing), axis=-1)
              != np.sort(ref_out["selection"], axis=-1)).any(axis=-1)
    margins = ref_out["margin"][differ]
    return {"differ_share": float(differ.mean()),
            "margin_worst": float(margins.max()) if margins.size else 0.0,
            "per_layer": differ.reshape(differ.shape[0], -1).sum(axis=1).tolist(),
            "decisions": int(differ.size)}


def _mode_gaps(ctx, module, ref, cfg, reference, run, params, x, groups,
               block):
    """One mode: the program's objective on ``x``, the reference under its
    routing, both parts' gaps. Returns (arithmetic gaps, selection gaps,
    per-parameter gradient ratios, finite, pairs dropped, the reference's
    outputs, the program's routing)."""
    import jax

    (loss, aux), grads = run(params, x)
    routing = np.asarray(aux["routing"])
    ref_out = reference(params, x, jax.numpy.float32, routing=routing)
    logits_rel, finite = lmpretrain.logits_gap(
        lambda p, h: module.apply(p, h, method="head"),
        lambda p, h: ref.logits_of(p, h, cfg),
        params, aux["hidden"], ref_out["hidden"], block)
    per_leaf = lmfit.group_ratios(jax.tree.leaves(grads), ref_out["grads"],
                                  groups)
    gaps = {"loss_abs": abs(float(loss) - ref_out["loss"]),
            "logits_rel": logits_rel, "grads_rel": float(per_leaf.max())}
    return (gaps, selection_gaps(routing, ref_out), per_leaf,
            finite and bool(np.isfinite(float(loss))),
            float(aux["pairs_dropped"]), ref_out, routing)


def check_objective(ctx, module, ref, est, train: np.ndarray,
                    held: np.ndarray, seed: int,
                    lower_reading: bool = False) -> dict:
    """Part (a), first half, for a routed model (this file's docstring); the
    rest is ``lmpretrain.check_objective``'s, step for step: the epoch
    replayed through the reference's AdamW from the reference's gradients
    (freely routed) and from the program's own objective's, and the held-out
    loss with the initial parameters."""
    import jax
    import jax.numpy as jnp

    tr = ctx.traffic
    batch = int(tr["batch"])
    order = np.asarray(est.epoch_order(0, len(train)))
    if sorted(order.tolist()) != list(range(len(train))):
        raise RuntimeError(f"epoch_order(0) is not a permutation: {order}")
    steps = len(train) // batch
    batches = [jnp.asarray(train[order[i * batch:(i + 1) * batch]])
               for i in range(steps)]
    x = batches[0]
    cfg = ref.config_of(ctx.config)
    block = int(tr["reference_token_block"])
    params = jax.jit(
        lambda r: module.init(r, x, None, method="loss")
    )(jax.random.PRNGKey(seed % (2 ** 31)))
    treedef = jax.tree.structure(params)
    leaf_names, groups = lmfit.grad_groups(params)
    ranks = [leaf.ndim for leaf in jax.tree.leaves(params)]
    matrices = np.array([all(ranks[i] >= 2 for i in idx) for idx in groups])
    reference = RoutedReference(ref, cfg, block)

    run_as = lmfit._objective(module)
    if not ctx.rehearsal and module.attn_impl == "flash":
        if "tpu_custom_call" not in run_as.lower(params, x).as_text():
            raise RuntimeError("no Mosaic custom call in the program's loss: "
                               "a stand-in ran in the flash kernel's place")
    tol = tr["arith_tolerance"]
    ok, worst, as_run = True, {}, None
    for mode in MODES:
        if mode == "matched":
            variant = module.clone(dtype=jnp.float32)
            with jax.default_matmul_precision("highest"):
                out = _mode_gaps(ctx, variant, ref, cfg, reference,
                                 lmfit._objective(variant), params, x, groups,
                                 block)
        else:
            out = _mode_gaps(ctx, module, ref, cfg, reference, run_as, params,
                             x, groups, block)
            ctx.say_time("the reference's first run (its compile inside)",
                         reference.seconds)
            as_run = out
        gaps, chosen, per_leaf, finite, dropped, _, _ = out
        limits, sel_limits = tol[mode], tr["selection_tolerance"][mode]
        held_ = (finite and dropped == 0
                 and all(gaps[k] <= limits[k] for k in GAPS)
                 and chosen["differ_share"] <= sel_limits["differ_share_max"]
                 and chosen["margin_worst"] <= sel_limits["margin_max"])
        ok = ok and held_
        far = np.argsort(-per_leaf)[:3]
        ctx.say(f"part (a) objective, {mode}, the fit's first batch of "
                f"{batch} x {x.shape[1] - 1} tokens. (i) selection: "
                f"{chosen['differ_share'] * chosen['decisions']:.0f} of "
                f"{chosen['decisions']} (token, layer) choices differ from "
                f"the reference's free choice (share "
                f"{chosen['differ_share']:.3g}, limit "
                f"{sel_limits['differ_share_max']}; by layer "
                f"{chosen['per_layer']}), the reference's largest margin "
                f"among them {chosen['margin_worst']:.3g} (limit "
                f"{sel_limits['margin_max']}); pairs dropped {dropped:.0f} "
                "(must be 0). (ii) arithmetic under the program's routing: "
                + ", ".join(f"{k} {gaps[k]:.3g} (limit {limits[k]})"
                            for k in GAPS)
                + f": {'ok' if held_ else 'FAIL'}; gradients farthest (L2, "
                "relative): " + ", ".join(
                    f"{leaf_names[i]} {per_leaf[i]:.3g}" for i in far)
                + f"; the matrices' farthest {per_leaf[matrices].max():.3g}")
        worst.update({f"{mode}.{k}": v for k, v in gaps.items()})
        worst.update({f"{mode}.{k}": chosen[k] for k in SELECTION})
        worst[f"{mode}.pairs_dropped"] = dropped
    (initial, _), _ = run_as(params, jnp.asarray(held[:batch]))
    initial = float(initial)
    ref_as_run, routing = as_run[5], as_run[6]
    low = None
    if lower_reading:
        # the second reading: the reference itself, computed in bf16 from
        # end to end under the SAME routing, held to the as_run limits
        low = reference(params, x, jnp.bfloat16, routing=routing)
        logits_rel, _ = lmpretrain.logits_gap(
            lambda p, h: ref.logits_of(p, h.astype(jnp.bfloat16), cfg,
                                       jnp.bfloat16),
            lambda p, h: ref.logits_of(p, h, cfg),
            params, low["hidden"], ref_as_run["hidden"], block)
        gaps = {"loss_abs": abs(low["loss"] - ref_as_run["loss"]),
                "logits_rel": logits_rel,
                "grads_rel": float(lmfit.group_ratios(
                    low["grads"], ref_as_run["grads"], groups).max())}
        limits = tol["as_run"]
        refused = [k for k in GAPS if gaps[k] > limits[k]]
        ctx.say("second reading, the reference in bf16 end to end against "
                "itself in float32, both under the program's routing: "
                + ", ".join(f"{k} {gaps[k]:.3g} (limit {limits[k]})"
                            for k in GAPS)
                + f": refused by {refused or 'nothing'}")
        worst.update({f"bf16_reference.{k}": v for k, v in gaps.items()})
    del as_run, ref_as_run

    # the reference's epoch, freely routed: its gradients through its AdamW
    hyper = ctx.config["model"]["adamw"]
    theta0 = [np.asarray(a, np.float32) for a in jax.tree.leaves(params)]
    del params
    t0 = time.perf_counter()
    theta_ref, losses = reference.epoch(
        theta0, treedef, batches, hyper,
        lambda p, rows: reference(p, rows, jnp.float32, states=False))
    ctx.say_time(f"the reference's epoch replayed ({steps} runs of it, "
                 f"{steps} AdamW steps on the host)", time.perf_counter() - t0)

    def own(p, rows):
        (loss, _), grads = run_as(p, rows)
        return {"loss": float(loss), "grads": [
            np.asarray(g, np.float32) for g in jax.tree.leaves(grads)]}

    t0 = time.perf_counter()
    theta_own, losses_own = reference.epoch(theta0, treedef, batches, hyper, own)
    del run_as
    ctx.say_time(f"the program's own epoch replayed ({steps} runs of its "
                 f"objective, {steps} AdamW steps on the host)",
                 time.perf_counter() - t0)
    a = {"groups": groups, "leaf_names": leaf_names, "treedef": treedef,
         "theta0": theta0,
         "theta_ref": theta_ref, "ref_epoch_loss": float(np.mean(losses)),
         "theta_own": theta_own, "own_epoch_loss": float(np.mean(losses_own)),
         "order": order}
    if lower_reading:
        theta_low, losses_low = reference.epoch(
            theta0, treedef, batches, hyper,
            lambda p, rows: reference(p, rows, jnp.bfloat16, states=False))
        del low
        gaps, _, _ = lmfit.step_gaps(theta_low, float(np.mean(losses_low)), a)
        del theta_low
        limits = tol["step"]
        step_refused = [k for k in limits if gaps[k] > limits[k]]
        ctx.say("second reading, the step: the bf16 reference's own epoch "
                "against the float32 one's: "
                + ", ".join(f"{k} {gaps[k]:.3g}" + (
                    f" (limit {limits[k]})" if k in limits else "")
                            for k in STEP_GAPS)
                + f": refused by {step_refused or 'nothing'}")
        worst.update({f"bf16_reference.step.{k}": v for k, v in gaps.items()})
        worst["bf16_reference.refused"] = bool(refused or step_refused)
        ok = ok and (worst["bf16_reference.refused"] or ctx.rehearsal)
    ctx.say_time("the reference alone (compile, a run per sequence of "
                 f"{steps} batches, AdamW on the host for every replay)",
                 reference.seconds)
    return {"ok": ok, "initial_held_out_loss": initial, **a, **worst}


def _note_the_stretchs_fences() -> None:
    """``kernel.moe_gmm_roofline`` needs the pairs the program counted IN THE
    TRACED STRETCH: the difference of its cumulative counters between the
    stretch's first and last fence. ``lmpretrain._fit`` starts and stops the
    profiler exactly there (an epoch's report is counted before its fence
    shows in ``history``) and has no seam to say so: for this process the
    two profiler calls also note the counters (``moe_costs.note_fence``)."""
    import jax

    for name in ("start_trace", "stop_trace"):
        call = getattr(jax.profiler, name)

        def noting(*args, _call=call, **kw):
            moe_costs.note_fence()
            return _call(*args, **kw)

        setattr(jax.profiler, name, noting)


def run_phase(ctx) -> None:
    # part (a)'s first half is this file's; the process runs one phase of
    # one cell and leaves, so the names are replaced for it alone
    lmpretrain.check_objective = check_objective
    if ctx.trace:
        _note_the_stretchs_fences()
    lmpretrain.run_phase(ctx)
