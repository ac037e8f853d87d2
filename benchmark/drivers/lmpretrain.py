"""Traffic kind ``lmpretrain``: ``lmfit``'s job (one ETL ->
``JaxEstimator.fit_on_etl`` that pre-trains a language model on packed token
sequences, ``loss="model"``), its phases, fence, window, trace and result
line, for ANY language model the configuration names. Nothing here names a
model:

``config["model"]["class"]``      the program's model; built by its
                                  ``from_config(config, **model["kwargs"])``
``config["model"]["reference"]``  the plain reference's module:
                                  ``config_of(config)``, ``loss_and_grads``,
                                  ``logits_of``, ``adamw_init``, ``adamw_step``
``config["model"]["costs"]``      needed FLOPs and bytes: ``step_flops``,
                                  ``kernels``, ``reader_values``

The model's ``loss(x, None, True)`` returns ``(loss, {"hidden": [B, T, D]})``,
the state its ``head`` reads; the reference's ``loss_and_grads(...,
with_states=True)`` the same. A next language model adds a configuration, a
reference, a costs module and a traffic file, and no driver. ``lmfit`` stays
as it is for the looped LM (its comparison is exit by exit); its helpers that
name no model are used from here, unedited.

A sample is ONE PACKED SEQUENCE: ``fit_samples_per_s`` counts sequences
between the first and the last epoch fence inside the window.

``correct`` never looks at the clock. (a) at the timed sizes, from the
parameters the fit starts from, on the batch the fit trains on first
(``JaxEstimator.epoch_order``): the objective the step program
differentiates against the reference (float32, highest): the loss, the
logits from the state the program held (max over tokens and ids, relative to
max |reference|), each parameter's gradient (L2, relative): ``as_run`` (bf16
compute) and ``matched`` (the program traced at float32 / highest), each at
the mix's limits. The step: ONE epoch through the estimator's own compiled
epoch program against two replays of that epoch through the reference's
AdamW on the host, in the same order: ``step_own`` from the gradients of the
program's own objective (loss, each parameter's change and its norm held),
``step`` from the reference's gradients (the change alone held: Adam's first
steps are lr x sign(g), and the paths part where rounding flips a small
gradient's sign). (b) the ETL's rows against the generated ones, exactly.
(c) held-out loss after the fixed warm-up fit is lower than with the initial
parameters by ``min_learning_margin``. (d) every loss the window's fit
reported is finite and every epoch counted train_rows // batch steps.
``--check-seeds`` (a process per seed) adds the second reading: the reference
itself computed in bf16 (its objective, and its own epoch through its AdamW)
must be refused by one of the limits.
"""

from __future__ import annotations

import gc
import importlib
import threading
import time

import numpy as np

from benchmark.drivers import lmfit
from benchmark.harness import layers, stats, tokens, xplane
from benchmark.harness.child import metric_dict
from benchmark.harness.peaks import peaks_for

GAPS = ("loss_abs", "logits_rel", "grads_rel")
MODES = ("as_run", "matched")
STEP_GAPS = lmfit.STEP_GAPS
# a seed of the check takes part (a) twice over (the bf16 reference compiles
# and runs beside the float32 one): one seed an invocation on the chip, as
# for ``lmfit`` and for the same reason (run.py's time limit)
MAX_CHECK_SEEDS_TIMED = 1

phases = lmfit.phases
check_phases = lmfit.check_phases


def _named(path: str):
    module, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def run_phase(ctx) -> None:
    seed = None
    if ctx.phase.startswith(lmfit.CHECK_PHASE):
        index = int(ctx.phase[len(lmfit.CHECK_PHASE):])
        most = lmfit.MAX_CHECK_SEEDS if ctx.rehearsal else MAX_CHECK_SEEDS_TIMED
        if len(ctx.check_seeds) > most:
            raise SystemExit(f"--check-seeds takes {most} seeds at most here")
        if index >= len(ctx.check_seeds):
            ctx.write(ctx.phase, {"correct": {}})
            return
        seed = ctx.check_seeds[index]
    # the model's class BEFORE any cluster or ETL actor is started: a
    # program that lacks it leaves at once, with nothing running
    name = ctx.config["model"]["class"]
    try:
        model_class = _named(name)
    except (ImportError, AttributeError) as exc:
        raise SystemExit(
            f"this program cannot run configuration {ctx.cell.config_name!r}: "
            f"it has no {name} ({exc})") from None
    lmfit._leave_after(ctx, lambda c: (
        _fit(c, model_class) if seed is None
        else _check_seed(c, model_class, seed)))


# -- part (a) ------------------------------------------------------------------


class Reference:
    """The reference's outputs on a batch, one sequence at a time (every
    sequence holds as many tokens, so the batch's loss and gradients are the
    means of the sequences'), gradients on the host so that the program's fit
    beside them; what comes back beside loss and gradients is ``hidden``
    [B, T, D], the state the head reads. ``seconds`` counts what it took."""

    def __init__(self, ref, cfg: dict, block: int):
        self.ref, self.cfg, self.block, self.seconds = ref, cfg, block, 0.0

    def _run(self, dtype):
        import jax

        key = ("reference", self.ref.__name__, repr(sorted(self.cfg.items())),
               self.block, str(dtype))
        if key not in lmfit._JITS:
            ref, cfg, block = self.ref, self.cfg, self.block
            lmfit._JITS[key] = jax.jit(lambda q, row: ref.loss_and_grads(
                q, row, cfg, block, True, dtype, with_states=True))
        return lmfit._JITS[key]

    def __call__(self, p, rows, dtype, states=True) -> dict:
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        run, batch = self._run(dtype), rows.shape[0]
        loss, grads, hidden = 0.0, None, []
        for i in range(batch):
            value, aux, g = run(p, rows[i:i + 1])
            g = [np.asarray(a, np.float32) for a in jax.tree.leaves(g)]
            loss += float(value) / batch
            grads = g if grads is None else [a + b for a, b in zip(grads, g)]
            if states:
                hidden.append(aux["hidden"])
        if batch > 1:
            grads = [a / batch for a in grads]
        out = {"loss": loss, "grads": grads}
        if states:
            out["hidden"] = jnp.concatenate(hidden, axis=0)
        self.seconds += time.perf_counter() - t0
        return out

    def epoch(self, theta0, treedef, batches, hyper, loss_and_grads, first=None):
        """An epoch from a copy of the leaves ``theta0`` through the
        reference's in-place AdamW on the host, batch after batch:
        ``loss_and_grads(params, batch)`` gives {"loss", "grads": leaves}
        (``first``: the first batch's, taken already; its gradients are
        dropped from it once used: 3.4 GB of the host's memory). Returns
        (the leaves after the epoch, each step's loss)."""
        import jax
        import jax.numpy as jnp

        # the reference's AdamW works in place: on copies
        leaves = [np.array(a, np.float32) for a in theta0]
        state = self.ref.adamw_init(leaves)
        losses, out = [], first
        for rows in batches:
            if out is None:
                p = jax.tree.unflatten(treedef,
                                       [jnp.asarray(a) for a in leaves])
                out = loss_and_grads(p, rows)
                del p
            t0 = time.perf_counter()  # the reference's runs count themselves
            losses.append(out["loss"])
            leaves, state = self.ref.adamw_step(
                leaves, out["grads"], state, hyper["learning_rate"],
                hyper["b1"], hyper["b2"], hyper["weight_decay"])
            self.seconds += time.perf_counter() - t0
            del out["grads"]
            out = None
        return leaves, losses


def logits_gap(head, head_ref, params, hidden, hidden_ref, block: int):
    """(max |logits - reference's| over max |reference's|, all finite), the
    logits ``block`` tokens at a time: whole they are 1.6 GB a side."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def part(p, h, h_ref):
        z, z_ref = head(p, h).astype(jnp.float32), head_ref(p, h_ref)
        return (jnp.abs(z - z_ref).max(), jnp.abs(z_ref).max(),
                jnp.isfinite(z).all())

    width = hidden.shape[-1]
    flat, flat_ref = hidden.reshape(-1, width), hidden_ref.reshape(-1, width)
    parts = np.array([
        [float(v) for v in part(params, flat[s:s + block],
                                flat_ref[s:s + block])]
        for s in range(0, flat.shape[0], block)])
    return float(parts[:, 0].max() / parts[:, 1].max()), bool(parts[:, 2].all())


def _gaps(module, ref, cfg, run, params, x, ref_out, groups, block):
    """The program's objective on batch ``x`` against the reference's
    outputs: {gap name: value}, per-parameter gradient ratios, finiteness."""
    import jax

    (loss, aux), grads = run(params, x)
    logits_rel, finite = logits_gap(
        lambda p, h: module.apply(p, h, method="head"),
        lambda p, h: ref.logits_of(p, h, cfg),
        params, aux["hidden"], ref_out["hidden"], block)
    per_leaf = lmfit.group_ratios(jax.tree.leaves(grads), ref_out["grads"],
                                  groups)
    return ({"loss_abs": abs(float(loss) - ref_out["loss"]),
             "logits_rel": logits_rel, "grads_rel": float(per_leaf.max())},
            per_leaf, finite and bool(np.isfinite(float(loss))))


def check_objective(ctx, module, ref, est, train: np.ndarray,
                    held: np.ndarray, seed: int,
                    lower_reading: bool = False) -> dict:
    """Part (a), first half (``lmfit.check_objective``'s, for a model with
    one set of logits): on the FIRST batch the fit will train on, with the
    parameters it starts from, the objective the step program
    differentiates against the reference, ``as_run`` and ``matched``; then
    the epoch replayed through the reference's AdamW, once from the
    reference's gradients and once from the program's own objective's, for
    ``lmfit.check_step``. Also the held-out loss with the initial
    parameters, for part (c)."""
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
    reference = Reference(ref, cfg, block)

    ref_out = reference(params, x, jnp.float32)
    ctx.say_time("the reference's first run (its compile inside)",
                 reference.seconds)
    run_as = lmfit._objective(module)
    if not ctx.rehearsal and module.attn_impl == "flash":
        if "tpu_custom_call" not in run_as.lower(params, x).as_text():
            raise RuntimeError("no Mosaic custom call in the program's loss: "
                               "a stand-in ran in the flash kernel's place")
    tol = tr["arith_tolerance"]
    ok, worst = True, {}
    for mode in MODES:
        if mode == "matched":
            variant = module.clone(dtype=jnp.float32)
            with jax.default_matmul_precision("highest"):
                gaps, per_leaf, finite = _gaps(
                    variant, ref, cfg, lmfit._objective(variant), params, x,
                    ref_out, groups, block)
        else:
            gaps, per_leaf, finite = _gaps(
                module, ref, cfg, run_as, params, x, ref_out, groups, block)
        limits = tol[mode]
        held_ = finite and all(gaps[k] <= limits[k] for k in GAPS)
        ok = ok and held_
        far = np.argsort(-per_leaf)[:3]
        ctx.say(f"part (a) objective, {mode}, the fit's first batch of "
                f"{batch} x {x.shape[1] - 1} tokens: "
                + ", ".join(f"{k} {gaps[k]:.3g} (limit {limits[k]})"
                            for k in GAPS)
                + f": {'ok' if held_ else 'FAIL'}; gradients farthest (L2, "
                "relative): " + ", ".join(
                    f"{leaf_names[i]} {per_leaf[i]:.3g}" for i in far)
                + f"; the matrices' farthest {per_leaf[matrices].max():.3g}")
        worst.update({f"{mode}.{k}": v for k, v in gaps.items()})
    (initial, _), _ = run_as(params, jnp.asarray(held[:batch]))
    initial = float(initial)
    if lower_reading:
        # the second reading: the reference itself, computed in bf16 from
        # end to end, held to the as_run limits. It has to be refused.
        low = reference(params, x, jnp.bfloat16)
        logits_rel, _ = logits_gap(
            lambda p, h: ref.logits_of(p, h.astype(jnp.bfloat16), cfg,
                                       jnp.bfloat16),
            lambda p, h: ref.logits_of(p, h, cfg),
            params, low["hidden"], ref_out["hidden"], block)
        gaps = {"loss_abs": abs(low["loss"] - ref_out["loss"]),
                "logits_rel": logits_rel,
                "grads_rel": float(lmfit.group_ratios(
                    low["grads"], ref_out["grads"], groups).max())}
        limits = tol["as_run"]
        refused = [k for k in GAPS if gaps[k] > limits[k]]
        ctx.say("second reading, the reference in bf16 end to end against "
                "itself in float32: "
                + ", ".join(f"{k} {gaps[k]:.3g} (limit {limits[k]})"
                            for k in GAPS)
                + f": refused by {refused or 'nothing'}")
        worst.update({f"bf16_reference.{k}": v for k, v in gaps.items()})

    # the reference's epoch: its gradients through its AdamW on the host
    hyper = ctx.config["model"]["adamw"]
    theta0 = [np.asarray(a, np.float32) for a in jax.tree.leaves(params)]
    del params
    t0 = time.perf_counter()
    theta_ref, losses = reference.epoch(
        theta0, treedef, batches, hyper,
        lambda p, rows: reference(p, rows, jnp.float32, states=False), ref_out)
    del ref_out
    ctx.say_time(f"the reference's epoch replayed ({steps - 1} more runs of "
                 f"it, {steps} AdamW steps on the host)",
                 time.perf_counter() - t0)

    def own(p, rows):
        # the program's own objective (as run) in the reference's place
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
        # the step's second reading: the bf16 reference's own epoch
        theta_low, losses_low = reference.epoch(
            theta0, treedef, batches, hyper,
            lambda p, rows: reference(p, rows, jnp.bfloat16, states=False), low)
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
        # the limits are set at the real size: at the rehearsal's, rounding
        # is smaller and the reading decides nothing
        ok = ok and (worst["bf16_reference.refused"] or ctx.rehearsal)
    ctx.say_time("the reference alone (compile, a run per sequence of "
                 f"{steps} batches, AdamW on the host for every replay)",
                 reference.seconds)
    return {"ok": ok, "initial_held_out_loss": initial, **a, **worst}


def warm_up(ctx, module, train, held, seed: int) -> dict:
    """A fit of a FIXED number of epochs through the cell's own runner: it
    compiles every shape the window uses, and part (c) reads its last
    held-out loss."""
    epochs = int(ctx.traffic["warmup_epochs"])
    est = lmfit.make_estimator(ctx, module, seed, epochs)
    t0 = time.perf_counter()
    history = est.fit_on_etl(train, held)
    ctx.say_time(f"warm-up fit ({epochs} epoch(s), compile "
                 f"{est.compile_seconds_:.1f} s inside)", time.perf_counter() - t0)
    ctx.say("warm-up fit, held-out loss after each epoch: " + ", ".join(
        f"{float(rec['eval_loss']):.4f}" for rec in history))
    stats_ = est.fit_stats_
    est.clear_staging_cache()
    return {"fitted": float(history[-1]["eval_loss"]),
            "peak_source": stats_.get("peak_source"),
            "flops_per_step_program": stats_.get("flops_per_step")}


def checks(ctx, module, ref, train, held, train_rows, held_rows, seed: int,
           lower_reading: bool = False):
    """Parts (a) and (c) around the warm-up fit, in ``lmfit.checks``'s
    order. Returns (a, c, the warm-up's facts, the seconds the check alone
    took: everything here but the warm-up fit)."""
    t0 = time.perf_counter()
    a = check_objective(ctx, module, ref,
                        lmfit.make_estimator(ctx, module, seed, 1),
                        train_rows, held_rows, seed, lower_reading)
    check_s = time.perf_counter() - t0
    gc.collect()
    warm = warm_up(ctx, module, train, held, seed)
    t0 = time.perf_counter()
    step = lmfit.check_step(ctx, module, train, held, seed, a)
    for key in ("theta0", "theta_ref", "theta_own"):
        del a[key]
    a.update(step, ok=a["ok"] and step["ok"])
    c = lmfit.fit_trains(ctx, a["initial_held_out_loss"], warm["fitted"])
    check_s += time.perf_counter() - t0
    ctx.say_time("part (a) in all (no job runs it: outside set-up)", check_s)
    return a, c, warm, check_s


def _start(ctx, model_class, seed: int):
    """What a run and a checked seed share: the device, the sequences, the
    ETL session and its query, part (b), the model and its reference."""
    dev = ctx.claim_device()
    c, tr = ctx.config, ctx.traffic
    t = time.perf_counter()
    table, raw = tokens.raw_frame(
        seed, int(tr["rows"]), int(tr["seq_len"]), int(c["vocab_size"]),
        float(tr["zipf_a"]), float(tr["bigram_tilt"]))
    ctx.say_time(f"{table.num_rows} sequences of {tr['seq_len']}+1 ids from "
                 f"seed {seed}", time.perf_counter() - t)
    session = lmfit.start_etl(ctx)
    train, held, query_s = lmfit.preprocess(ctx, session, table, seed)
    ctx.say_time("the query (last_query_stats)", query_s)
    part_b, rows = lmfit.check_data(ctx, raw, train, held)
    module = model_class.from_config(c, **c["model"]["kwargs"])
    ref = importlib.import_module(c["model"]["reference"])
    return dev, train, held, query_s, part_b, rows, module, ref


def _check_seed(ctx, model_class, seed: int) -> None:
    _, train, held, _, b, rows, module, ref = _start(ctx, model_class, seed)
    a, cc, warm, _ = checks(ctx, module, ref, train, held, rows["train"],
                            rows["held_out"], seed, lower_reading=True)
    ctx.say(
        f"seed {seed}: (a) " + "; ".join(
            f"{mode}: " + " ".join(f"{k} {a[f'{mode}.{k}']:.3g}" for k in GAPS)
            for mode in ("as_run", "matched", "bf16_reference"))
        + "".join(
            f"; {key}: " + " ".join(f"{k} {a[f'{key}.{k}']:.3g}"
                                    for k in STEP_GAPS)
            for key in ("step_own", "step", "bf16_reference.step"))
        + f"; bf16 reference refused={a['bf16_reference.refused']} | "
        f"(b) exact={b['ok']} | (c) held-out "
        f"{a['initial_held_out_loss']:.4f} -> {warm['fitted']:.4f} "
        f"margin {cc['margin']:.4f}")
    ctx.write(ctx.phase, {
        "correct": {f"seed_{seed}": a["ok"] and b["ok"] and cc["ok"]},
        "device": ctx.device})


# -- the run --------------------------------------------------------------------


def _fit(ctx, model_class) -> None:
    import jax

    from raydp_tpu import obs

    c, tr = ctx.config, ctx.traffic
    batch, train_rows = int(tr["batch"]), int(tr["train_rows"])
    seq_len = int(tr["seq_len"])
    steps_per_epoch = train_rows // batch
    seed = ctx.seed
    dev, train, held, query_s, part_b, rows, module, ref = _start(
        ctx, model_class, seed)
    part_a, part_c, warm, check_s = checks(
        ctx, module, ref, train, held, rows["train"], rows["held_out"], seed)
    if not ctx.rehearsal and warm["peak_source"] != "tpu-table":
        raise RuntimeError(f"estimator's peak_source is "
                           f"{warm['peak_source']!r}, not 'tpu-table'")
    ctx.say(f"device memory peak after the checks and the warm-up fit: "
            f"{ctx.memory_peak_bytes()} bytes")

    est = lmfit.make_estimator(ctx, module, seed, num_epochs=1_000_000)
    failure = []
    # set-up's garbage (GBs of reference parameters among it) is collected
    # here, not by a full collection that lands inside the window
    gc.collect()
    gc.freeze()

    def job():
        try:
            est.fit_on_etl(train, held)
        except BaseException as exc:  # noqa: BLE001 - reported by the watcher
            failure.append(exc)

    thread = threading.Thread(target=job, name="window-fit", daemon=True)
    t_fit = time.perf_counter()
    thread.start()
    fences = []  # (perf_counter, epochs fenced)
    t_open = wall_open = None
    trace_dir = ctx.path("trace")
    trace_state = "wait" if ctx.trace else "off"
    trace_stop_at = steps_at_start = steps_in_trace = None
    annotation = None
    while True:
        done = len(est.history)
        now = time.perf_counter()
        if done > len(fences):
            fences.append((now, done))
            if t_open is None:
                t_open, wall_open = now, time.time()
                ctx.say_time("the window's fit, start to its first fence "
                             "(the window opens)", now - t_fit)
            elif trace_state == "wait" and len(fences) >= 2:
                # at a fence everything dispatched has completed
                steps_at_start = done * steps_per_epoch
                jax.profiler.start_trace(trace_dir)
                annotation = jax.profiler.TraceAnnotation(xplane.WINDOW_EVENT)
                annotation.__enter__()
                trace_state = "on"
                trace_stop_at = done + int(tr["trace_epochs"])
            elif trace_state == "on" and done >= trace_stop_at:
                annotation.__exit__(None, None, None)
                jax.profiler.stop_trace()
                steps_in_trace = done * steps_per_epoch - steps_at_start
                trace_state = "done"
        if failure:
            raise RuntimeError(f"the window's fit failed: {failure[0]!r}")
        if (t_open is not None and now >= t_open + ctx.seconds
                and trace_state in ("off", "done")):
            break
        time.sleep(0.002)
    # part (a) is the benchmark's own work: a job's set-up is what is left
    setup_s = wall_open - ctx.t0 - check_s
    rate = stats.fenced_rate([(t, e * steps_per_epoch * batch)
                              for t, e in fences], t_open, ctx.seconds)
    if rate is None:
        raise RuntimeError(
            f"fewer than two epoch fences in a window of {ctx.seconds} s: "
            "an epoch is too long for this window")

    # part (d): what the window's fit reported, epoch by epoch
    records = list(est.history)[:fences[-1][1]]
    losses, steps_ok = [], True
    for rec in records:
        train_loss = rec["train_loss"]
        if isinstance(train_loss, tuple):
            steps_ok = steps_ok and int(train_loss[1]) == steps_per_epoch
            train_loss = float(np.asarray(train_loss[0])) / max(train_loss[1], 1)
        losses += [float(train_loss), float(rec["eval_loss"])]
    part_d = bool(losses) and bool(np.isfinite(losses).all()) and steps_ok
    ctx.say(f"part (d) the window: {len(records)} epochs reported, every "
            f"train and held-out loss finite: {bool(np.isfinite(losses).all())}"
            f", every epoch counted {steps_per_epoch} steps: {steps_ok}; last "
            f"held-out loss {losses[-1]:.4f}")

    result = {
        "attempted": len(records), "failed": 0,
        "correct": {"a_arithmetic": part_a["ok"], "b_data": part_b["ok"],
                    "c_fit_trains": part_c["ok"], "d_window": part_d},
        "device": {**dev, "memory_peak_bytes": ctx.memory_peak_bytes()},
    }
    if ctx.rehearsal:
        ctx.write(ctx.phase, result)
        return
    ctx.say(f"window: {rate['work']:.0f} sequences between "
            f"{rate['fences']} fences over {rate['elapsed_s']:.3f} s = "
            f"{rate['rate']:.4f} sequences/s = "
            f"{rate['rate'] * seq_len:.1f} tokens/s; set-up {setup_s:.2f} "
            f"s (part (a)'s {check_s:.2f} s left out); on {dev['kind']} "
            f"x{dev['count']}")
    if not ctx.trace:
        result["metrics"] = metric_dict(
            ctx.cell, {"fit_samples_per_s": rate["rate"], "setup_s": setup_s})
        ctx.write(ctx.phase, result)
        return
    summary = xplane.reduce_trace(xplane.find_xplane(trace_dir))
    xplane.keep_copy(trace_dir, ctx)
    peaks = peaks_for(dev["kind"])
    costs = importlib.import_module(c["model"]["costs"])
    flops = costs.step_flops(c, batch, seq_len)
    snap = obs.metrics.snapshot()
    ctx.say("FLOPs of a step: from shapes (" + c["model"]["costs"] + ") "
            + ", ".join(f"{k} {v:.4g}" for k, v in flops.items())
            + f"; the program's own count (fit_facts' flops_per_row x "
            f"batch) {warm['flops_per_step_program']}; program gauges "
            + ", ".join(f"{k}={snap[k]['value']:.6g}" for k in sorted(snap)
                        if k.startswith(("model.", "estimator.tokens"))))
    sources = {
        "values": {
            "etl_query_s": query_s,
            "steps_in_trace": steps_in_trace,
            "vocab_size": int(c["vocab_size"]),
            "model_flops_utilization_pct": 100.0 * flops["total"]
            * rate["rate"] / batch / peaks["flops_per_s"],
            **costs.reader_values(c, batch, seq_len),
        },
        "trace": summary, "peaks": peaks,
        "kernels": costs.kernels(c, batch, seq_len),
    }
    result["metrics"] = layers.read_all(ctx.cell, sources)
    result["device"].update(busy_s=summary.busy_s, window_s=summary.window_s)
    result["breakdown"] = summary.breakdown()
    ctx.write(ctx.phase, result)
