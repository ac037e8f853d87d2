"""Traffic kind ``lmpretrain_routed_placed``: ``lmpretrain_routed``'s job,
phases, window, trace, result line and comparison, unedited, for a routed
model WHOSE ROUTER TAKES NO BIAS. What that driver cannot do here, and this
one adds (each replaces one name, in this process alone, as
``lmpretrain_routed`` does):

(1) SET-UP PLACES THE EXPERTS. No rule evens the load of a router without a
bias, so what the seeded routers hand this chip's experts differs by seed
(0.07 to 2.0 x the even share a layer, 0 to 2 of 4 layers past the likely
rows' bound: my chip runs, PR 42) and ``fit_samples_per_s`` with it. A group
answers with PLACEMENT: which experts sit on which chip is chosen from the
observed load. ``_start`` hands back the model
``model.placed_by_load(rng, the training batches)`` gives (one forward
program, a run a layer and batch; counted in ``setup_s``): the same seed
then gives the estimator, part (a) and the reference the placed parameters.
A program whose model cannot place leaves at once, before any cluster.

(2) A FOURTH ARITHMETIC GAP, ``token_loss_rms``: the root mean square, over
the batch's tokens, of the program's loss OF EACH TOKEN less the
reference's (from the state each held, through its own head and its own
cross-entropy). ``loss_abs`` is the gap of two MEANS over 32,768 tokens, in
which a bf16 model's rounding cancels by chance (the bf16 reference read
0.0029 to 0.031 against the program's 2e-6 to 0.0022, my chip runs, PR 42):
it does not tell the two apart on every seed. Token by token nothing
cancels. ``as_run`` and ``matched`` hold it under their limits.

(3) THE SECOND READING WITHIN THE HOST'S MEMORY (``--check-seeds``): after
``lmpretrain_routed.check_objective`` has returned, from the parameters it
returned, with both replays' leaves on disk meanwhile: the bf16
reference's epoch then runs beside one copy of 656 M parameters, not three
and a dead set of gradients. The bf16 reference must be refused by one
of the FOUR limits of ``as_run`` or by the step's."""

from __future__ import annotations

import ctypes
import gc
import os
import resource
import time

import numpy as np

from benchmark.drivers import lmfit, lmpretrain
from benchmark.drivers import lmpretrain_routed as routed
from benchmark.drivers.lmpretrain import (  # noqa: F401 - a driver's surface
    STEP_GAPS, _named, check_phases, phases)
from benchmark.drivers.lmpretrain_routed import (
    RoutedReference, selection_gaps)

GAPS = lmpretrain.GAPS + ("token_loss_rms",)
_routed_check_objective = routed.check_objective
_plain_start = lmpretrain._start


def token_losses(losses_of, params, hidden, targets, block: int) -> np.ndarray:
    """float32 [tokens]: every token's cross-entropy, ``losses_of(params, h
    [block, D], y [block])`` over ``block`` tokens at a time (whole, the
    logits are 5 GB)."""
    import jax

    part = jax.jit(losses_of)
    flat = hidden.reshape(-1, hidden.shape[-1])
    flat_y = np.asarray(targets).reshape(-1)
    return np.concatenate([
        np.asarray(part(params, flat[s:s + block], flat_y[s:s + block]),
                   np.float32)
        for s in range(0, flat.shape[0], block)])


def program_token_losses(module):
    """The program's: its own head's float32 logits, a float32 softmax."""
    import jax
    import jax.numpy as jnp

    def losses_of(p, h, y):
        z = module.apply(p, h, method="head").astype(jnp.float32)
        return -jnp.take_along_axis(
            jax.nn.log_softmax(z, axis=-1), y[:, None], axis=-1)[:, 0]

    return losses_of


def reference_token_losses(ref, cfg, dtype):
    """The reference's own, in ``dtype`` from the state to the loss."""
    return lambda p, h, y: ref.token_losses(
        p, h.astype(dtype)[None], y[None], cfg, 0, dtype)[0]


def _rms(a: np.ndarray, b: np.ndarray) -> float:
    return float(np.sqrt(np.mean((a.astype(np.float64) - b) ** 2)))


def _mode_gaps(ctx, module, ref, cfg, reference, run, params, x, groups,
               block):
    """``lmpretrain_routed._mode_gaps`` with the fourth gap."""
    import jax
    import jax.numpy as jnp

    (loss, aux), grads = run(params, x)
    routing = np.asarray(aux["routing"])
    ref_out = reference(params, x, jnp.float32, routing=routing)
    logits_rel, finite = lmpretrain.logits_gap(
        lambda p, h: module.apply(p, h, method="head"),
        lambda p, h: ref.logits_of(p, h, cfg),
        params, aux["hidden"], ref_out["hidden"], block)
    per_leaf = lmfit.group_ratios(jax.tree.leaves(grads), ref_out["grads"],
                                  groups)
    targets = x[:, 1:]
    gaps = {"loss_abs": abs(float(loss) - ref_out["loss"]),
            "logits_rel": logits_rel, "grads_rel": float(per_leaf.max()),
            "token_loss_rms": _rms(
                token_losses(program_token_losses(module), params,
                             aux["hidden"], targets, block),
                token_losses(reference_token_losses(ref, cfg, jnp.float32),
                             params, ref_out["hidden"], targets, block))}
    return (gaps, selection_gaps(routing, ref_out), per_leaf,
            finite and bool(np.isfinite(float(loss))),
            float(aux["pairs_dropped"]), ref_out, routing)


def _host_peak_gb() -> float:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20


def bf16_reference_gaps(ref, cfg, reference, params, x, routing, exact,
                        groups, block) -> dict:
    """The four gaps of the reference run in bf16 from end to end under
    ``routing`` against ``exact``, its float32 run under the same."""
    import jax.numpy as jnp

    low = reference(params, x, jnp.bfloat16, routing=routing)
    logits_rel, _ = lmpretrain.logits_gap(
        lambda p, h: ref.logits_of(p, h.astype(jnp.bfloat16), cfg,
                                   jnp.bfloat16),
        lambda p, h: ref.logits_of(p, h, cfg),
        params, low["hidden"], exact["hidden"], block)
    targets = x[:, 1:]
    return {"loss_abs": abs(low["loss"] - exact["loss"]),
            "logits_rel": logits_rel,
            "grads_rel": float(lmfit.group_ratios(
                low["grads"], exact["grads"], groups).max()),
            "token_loss_rms": _rms(
                token_losses(reference_token_losses(ref, cfg, jnp.bfloat16),
                             params, low["hidden"], targets, block),
                token_losses(reference_token_losses(ref, cfg, jnp.float32),
                             params, exact["hidden"], targets, block))}


def second_reading(ctx, module, ref, a: dict, train: np.ndarray) -> dict:
    """The reference itself in bf16 from end to end, under the program's
    ``as_run`` routing, held to the four ``as_run`` limits; then its own
    epoch through its AdamW, held to the step's. One of them has to refuse
    it. Fills what ``lmpretrain._check_seed`` prints."""
    import jax
    import jax.numpy as jnp

    tr, batch = ctx.traffic, int(ctx.traffic["batch"])
    order, treedef, groups = a["order"], a["treedef"], a["groups"]
    batches = [jnp.asarray(train[order[i * batch:(i + 1) * batch]])
               for i in range(len(train) // batch)]
    x = batches[0]
    cfg, block = ref.config_of(ctx.config), int(tr["reference_token_block"])
    # neither replay's leaves are read before the bf16 epoch has run: on
    # disk meanwhile, and what the allocator still holds goes back
    spilled = {key: ctx.path(key + ".npz")
               for key in ("theta_ref", "theta_own")}
    for key, path in spilled.items():
        np.savez(path, *a[key])
        a[key] = None
    gc.collect()
    ctypes.CDLL("libc.so.6").malloc_trim(0)
    params = jax.tree.unflatten(treedef, [jnp.asarray(t) for t in a["theta0"]])
    reference = RoutedReference(ref, cfg, block)
    (_, aux), _ = lmfit._objective(module)(params, x)
    routing = np.asarray(aux["routing"])
    del aux
    exact = reference(params, x, jnp.float32, routing=routing)
    gaps = bf16_reference_gaps(ref, cfg, reference, params, x, routing, exact,
                               groups, block)
    del exact, params
    gc.collect()
    limits = tr["arith_tolerance"]["as_run"]
    refused = [k for k in GAPS if gaps[k] > limits[k]]
    ctx.say("second reading, the reference in bf16 end to end against "
            "itself in float32, both under the program's routing: "
            + ", ".join(f"{k} {gaps[k]:.3g} (limit {limits[k]})" for k in GAPS)
            + f": refused by {refused or 'nothing'}")
    out = {f"bf16_reference.{k}": v for k, v in gaps.items()}

    theta_low, losses_low = reference.epoch(
        a["theta0"], treedef, batches, ctx.config["model"]["adamw"],
        lambda p, rows: reference(p, rows, jnp.bfloat16, states=False))
    for key, path in spilled.items():
        with np.load(path) as kept:
            a[key] = [kept[f"arr_{i}"] for i in range(len(a["theta0"]))]
        os.remove(path)
    step, _, _ = lmfit.step_gaps(theta_low, float(np.mean(losses_low)), a)
    del theta_low
    limits = tr["arith_tolerance"]["step"]
    step_refused = [k for k in limits if step[k] > limits[k]]
    ctx.say("second reading, the step: the bf16 reference's own epoch "
            "against the float32 one's: "
            + ", ".join(f"{k} {step[k]:.3g}" + (
                f" (limit {limits[k]})" if k in limits else "")
                        for k in STEP_GAPS)
            + f": refused by {step_refused or 'nothing'}; the host's memory "
            f"peak so far {_host_peak_gb():.1f} GiB")
    out.update({f"bf16_reference.step.{k}": v for k, v in step.items()})
    out["bf16_reference.refused"] = bool(refused or step_refused)
    # the limits are set at the real size: the rehearsal's decides nothing
    out["ok"] = a["ok"] and (out["bf16_reference.refused"] or ctx.rehearsal)
    return out


def check_objective(ctx, module, ref, est, train: np.ndarray,
                    held: np.ndarray, seed: int,
                    lower_reading: bool = False) -> dict:
    routed.GAPS, routed._mode_gaps = GAPS, _mode_gaps  # the fourth gap
    a = _routed_check_objective(ctx, module, ref, est, train, held, seed)
    ctx.say(f"part (a)'s objective and replays: the host's memory peak so "
            f"far {_host_peak_gb():.1f} GiB")
    if lower_reading:
        a.update(second_reading(ctx, module, ref, a, train))
    return a


def _start_placed(ctx, model_class, seed: int):
    """``lmpretrain._start``, then the placement (this file's (1))."""
    import jax
    import jax.numpy as jnp

    dev, train, held, query_s, part_b, rows, module, ref = _plain_start(
        ctx, model_class, seed)
    batch = int(ctx.traffic["batch"])
    t0 = time.perf_counter()
    module, before, after = module.placed_by_load(
        jax.random.PRNGKey(seed % (2 ** 31)),
        [jnp.asarray(rows["train"][i:i + batch])
         for i in range(0, len(rows["train"]), batch)])
    ctx.say(
        "the experts placed by the load of the seeded routers on the "
        f"{len(rows['train'])} training sequences: this chip's experts' "
        "pairs over the even share, by expert layer, as seeded "
        + ", ".join(f"{v:.3f}" for v in before) + " and as placed "
        + ", ".join(f"{v:.3f}" for v in after)
        + " (each layer placed under the placement of those before it)")
    ctx.say_time("the placement (one forward program compiled, a run a "
                 "layer and batch)", time.perf_counter() - t0)
    return dev, train, held, query_s, part_b, rows, module, ref


def run_phase(ctx) -> None:
    name = ctx.config["model"]["class"]
    try:
        placing = hasattr(_named(name), "placed_by_load")
    except (ImportError, AttributeError):
        placing = False
    if not placing:
        # before any cluster or ETL actor: nothing is left running
        raise SystemExit(
            f"this program cannot run configuration {ctx.cell.config_name!r}"
            f": its {name} places no experts (no placed_by_load)")
    lmpretrain._start = _start_placed
    lmpretrain.check_objective = check_objective
    if ctx.trace:
        routed._note_the_stretchs_fences()
    lmpretrain.run_phase(ctx)
