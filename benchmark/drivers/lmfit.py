"""Traffic kind ``lmfit``: one ETL -> ``JaxEstimator.fit_on_etl`` job that
pre-trains a language model on packed token sequences (the frame's one
``FixedSizeList<int32>[seq_len + 1]`` column, staged as one int32
``[batch, seq_len + 1]`` feature; ``label_column=None``, ``loss="model"``).

A sample is ONE PACKED SEQUENCE of ``seq_len`` predicted tokens:
``fit_samples_per_s`` counts sequences, measured exactly as the ``fit`` kind
measures rows (first to last fence inside the window); tokens per second is
``seq_len`` times it.

One phase, one process (``fit.py``'s phase structure and process discipline):

set-up   sequences from the seed -> ``init_etl`` -> the query (``random_split``
         into training and held-out rows, ``limit``) -> correctness part (b)
         data -> a warm-up fit of a fixed number of epochs through the
         cell's own runner, which compiles the cell's shapes and decides
         part (c). Part (a) runs around the warm-up fit and is timed apart:
         ``setup_s`` leaves its seconds out (the reference, two programs no
         job runs, one epoch replayed: the benchmark's own work).
window   ONE ``fit_on_etl(train, held_out)`` with more epochs than any window
         holds, in a thread. Each epoch ends in the estimator's own held-out
         evaluation, whose loss fetch drains the device: a fence. The rate is
         the sequences between the first fence at or after the window opens
         and the last one before it closes, over the time between them.
trace    (``--trace 1``) from one fence to the fence ``trace_epochs`` epochs
         later, so that the steps the program counted as completed in
         between (``estimator.steps_completed``) are exactly the steps the
         trace holds: a looped body runs 4 or 24 times a step and defeats a
         count by commonest call count.

``correct`` never looks at the clock. (a) against
``benchmark/reference/ouro.py`` (float32, highest), at the timed sizes, from
the parameters the fit starts from, on the batches the fit trains on first
(``JaxEstimator.epoch_order``). The objective the step program
differentiates (``loss`` through the scanned loop and the chunked exit
loss): the loss, each exit's logits from the state the scan held (max over
tokens, relative to max |reference|), the exit distribution p, each
parameter's gradient (L2 ratio, the shared layers' included; the gate's bias
with the gate's vector): ``matched`` (the program traced at float32 /
highest) and ``as_run`` (bf16 compute), each at the mix's limits. The step:
ONE epoch through the estimator's own compiled epoch program
(``make_train_step`` in the scan runner, donation, the configuration's
optimizer) against two replays of that epoch through the reference's AdamW
on the host, in the same order. ``step_own``: the gradients are those of the
program's own objective as run (the one held to the reference above), so
the replay follows the program's path and the epoch's mean training loss,
each parameter's change (L2 distance over the replay's change) and its norm
are held tightly: the optimizer, the scan, donation, the order, the loss's
accounting. ``step``: the gradients are the reference's; Adam's first steps
are lr x sign(g), so the two paths part where bf16 rounding flips a small
gradient's sign, and only the parameters' change is held to it (the loss
and the norm along a path that has parted are printed, not judged).
(b) the ETL's rows against the generated ones, exactly. (c) held-out loss
after the fixed warm-up fit is lower than with the initial parameters by the
mix's ``min_learning_margin``. (d) every loss the window's fit reported is
finite and every epoch counted exactly train_rows // batch steps.
``--check-seeds`` (a process per seed) adds a second reading: the reference
itself computed in bf16 (its objective, and its own epoch through its
AdamW) must be refused by one of the limits.
"""

from __future__ import annotations

import gc
import os
import threading
import time

import numpy as np

from benchmark.harness import layers, lm_costs, stats, tokens, xplane
from benchmark.harness.child import metric_dict
from benchmark.harness.peaks import peaks_for

GAPS = ("loss_abs", "logits_rel", "mass_abs", "grads_rel")
MODES = ("as_run", "matched")  # tools/lm_gaps.py reads as_run alone
STEP_GAPS = ("loss_abs", "change_rel", "change_norm")


def phases(trace: bool):
    return ["fit"]


MAX_CHECK_SEEDS = 16
# run.py gives a whole invocation 1150 s. At the timed sizes a run's part
# (a) takes 330-400 s, and the check adds the bf16 reading to it (another
# compile of the reference, its objective and its own epoch: 467 s for the
# reference's two precisions on one seed) and the warm-up fit: about 600 s
# a seed, so a second seed would pass the limit
MAX_CHECK_SEEDS_TIMED = 1
CHECK_PHASE = "check_seed_"


def check_phases():
    """``run.py --check-seeds``: parts (a) with the second reading, (b) and
    (c), each seed in a process of its own, as a run is. The parent does not
    hand the seeds to this function, so there is a phase per possible seed;
    one past the list leaves at once, without touching jax. On the chip an
    invocation takes ``MAX_CHECK_SEEDS_TIMED`` seed: run.py's time limit
    kills a phase's process alone, the ETL actors it started keep its output
    pipe open, and run.py then waits on that pipe for ever (PERF.md,
    Findings, PR 27: what looked like a hang of the chip)."""
    return [f"{CHECK_PHASE}{i}" for i in range(MAX_CHECK_SEEDS)]


def run_phase(ctx) -> None:
    seed = None
    if ctx.phase.startswith(CHECK_PHASE):
        index = int(ctx.phase[len(CHECK_PHASE):])
        most = MAX_CHECK_SEEDS if ctx.rehearsal else MAX_CHECK_SEEDS_TIMED
        if len(ctx.check_seeds) > most:
            raise SystemExit(f"--check-seeds takes {most} seeds at most here")
        if index >= len(ctx.check_seeds):
            ctx.write(ctx.phase, {"correct": {}})
            return
        seed = ctx.check_seeds[index]
    try:
        from raydp_tpu.models import looplm  # noqa: F401
    except ImportError as exc:
        raise SystemExit(
            f"this program cannot run configuration {ctx.cell.config_name!r}: "
            f"it has no looped LM ({exc})") from None
    _leave_after(ctx, _fit if seed is None else lambda c: _check_seed(c, seed))


# -- set-up pieces (the run and --check-seeds share them) --------------------


def reference_cfg(ctx) -> dict:
    c = ctx.config
    return {"num_attention_heads": c["num_attention_heads"],
            "rope_theta": float(c["rope_theta"]),
            "rms_norm_eps": float(c["rms_norm_eps"]),
            "total_ut_steps": int(c["total_ut_steps"]),
            "entropy_beta": float(c["model"]["entropy_beta"])}


def make_model(ctx):
    import jax.numpy as jnp

    from raydp_tpu.models import LoopLM

    c, m = ctx.config, ctx.config["model"]
    if c["hidden_size"] != c["num_attention_heads"] * c["head_dim"]:
        raise ValueError("hidden_size is not num_attention_heads x head_dim")
    if c["num_key_value_heads"] != c["num_attention_heads"]:
        raise ValueError("the looped LM is plain multi-head attention")
    return LoopLM(
        vocab_size=c["vocab_size"], hidden_size=c["hidden_size"],
        num_heads=c["num_attention_heads"], num_layers=c["num_hidden_layers"],
        intermediate_size=c["intermediate_size"],
        loop_steps=c["total_ut_steps"], rope_theta=float(c["rope_theta"]),
        rms_eps=float(c["rms_norm_eps"]),
        entropy_beta=float(m["entropy_beta"]), attn_impl=m["attn_impl"],
        dtype=jnp.dtype(m["compute_dtype"]), remat=bool(m["remat"]),
        loss_chunk=int(m["loss_chunk"]))


def start_etl(ctx):
    import raydp_tpu

    executors = int(ctx.traffic.get("executors", 1))
    session = raydp_tpu.init_etl(
        "benchmark", num_executors=executors, executor_cores=1,
        executor_memory="1G")
    ctx.say(f"init_etl: {executors} executor(s) x 1 core")
    return session


def stop_etl() -> None:
    """Executors, then the head: nothing of the run outlives it."""
    import raydp_tpu
    from raydp_tpu.cluster import api as cluster

    try:
        raydp_tpu.stop_etl()
    finally:
        cluster.shutdown()


def preprocess(ctx, session, table, seed: int):
    """The query: training and held-out rows by ``random_split``, cut to the
    mix's counts. Returns (train_df, held_df, the query's seconds by the
    program's own ``last_query_stats``)."""
    tr = ctx.traffic
    df = session.from_arrow(table, num_partitions=2)
    train_df, held_df = df.random_split([0.5, 0.5], seed=seed % (2 ** 31))
    train = train_df.limit(int(tr["train_rows"]))
    held = held_df.limit(int(tr["held_out_rows"]))
    return train, held, float(session.last_query_stats["seconds"])


def frame_rows(df):
    """(seq_id [N], tokens int32 [N, seq_len + 1]) of an ETL frame."""
    t = df.to_arrow()
    col = t.column(tokens.TOKENS).combine_chunks()
    width = col.type.list_size
    ids = col.flatten().to_numpy(zero_copy_only=False).reshape(len(col), width)
    return t.column(tokens.SEQ_ID).to_numpy(zero_copy_only=False), ids


def check_data(ctx, raw: np.ndarray, train_df, held_df):
    """Part (b): every row the ETL hands over is the generated row of its
    ``seq_id``, bit for bit; the two frames share no row and have the mix's
    counts. Returns (part, {"train" | "held_out": the frame's rows})."""
    tr = ctx.traffic
    bad, seen = [], []
    out = {}
    for name, df, want in (("train", train_df, int(tr["train_rows"])),
                           ("held_out", held_df, int(tr["held_out_rows"]))):
        seq, ids = frame_rows(df)
        out[name] = ids
        if len(seq) != want:
            bad.append(f"{name}: {len(seq)} rows, not {want}")
        if ids.dtype != np.int32 or not np.array_equal(ids, raw[seq]):
            bad.append(f"{name}: tokens differ from the generated rows")
        seen += seq.tolist()
    if len(set(seen)) != len(seen):
        bad.append("training and held-out rows overlap")
    ctx.say(f"part (b) data: {len(seen)} rows of {raw.shape[1]} int32 ids "
            f"equal to the generated ones, disjoint: "
            f"{'ok' if not bad else 'FAIL ' + '; '.join(bad)}")
    return {"ok": not bad}, out


def grad_groups(params):
    """([name], [[leaf index]]): the tree's leaves grouped into parameters,
    which are compared one by one. A scalar leaf (the gate's bias) goes
    with the leaves of the dict that holds it (the gate's vector): the L2
    ratio of ONE number is its relative error, and the bias's gradient is a
    sum over all tokens that nearly cancels on some batches (0.157 on one
    seed, 0.03 on the others, with the gate's vector at 0.04)."""
    import jax

    flat = jax.tree_util.tree_leaves_with_path(params)
    with_scalar = {path[:-1] for path, leaf in flat if leaf.ndim == 0}
    groups: dict = {}
    for i, (path, _) in enumerate(flat):
        key = path[:-1] if path[:-1] in with_scalar else path
        groups.setdefault(key, []).append(i)
    return [jax.tree_util.keystr(k) for k in groups], list(groups.values())


def group_ratios(got, want, groups) -> np.ndarray:
    """Per group: the L2 distance of ``got`` from ``want`` over ``want``'s
    L2 norm (lists of leaves, device or host arrays)."""
    import jax
    import jax.numpy as jnp

    parts = jax.jit(lambda a, b: jnp.stack(
        [((a - b) ** 2).sum(), (b ** 2).sum()]))
    sq = np.array([np.asarray(parts(a, jnp.asarray(b)), np.float64)
                   for a, b in zip(got, want)])
    return np.array([np.sqrt(sq[idx, 0].sum() / max(sq[idx, 1].sum(), 1e-40))
                     for idx in groups])


# jitted programs by what they compute: a process that checks several seeds
# (tools/lm_gaps.py) compiles each once
_JITS: dict = {}


def _objective(module):
    """The step program's own objective, as ``make_train_step`` takes it
    (``module.apply(..., method="loss")``: the scanned loop, the chunked
    exit loss), with its gradients and with what the scan held at every
    loop step: jitted ``(params, x) -> ((loss, aux), grads)``."""
    import jax

    if ("objective", module) not in _JITS:
        _JITS["objective", module] = jax.jit(jax.value_and_grad(
            lambda p, x: module.apply(p, x, None, True, method="loss"),
            has_aux=True))
    return _JITS["objective", module]


class Reference:
    """The reference's outputs on a batch, one sequence at a time (every
    sequence holds as many tokens, so the batch's loss and gradients are the
    means of the sequences'): at the real size its float32 attention takes
    5 GB a sequence. Gradients go to the host (2 GB there) so that the
    program's fit beside them. ``seconds`` counts what it took."""

    def __init__(self, cfg: dict, block: int):
        self.cfg, self.block, self.seconds = cfg, block, 0.0

    def _run(self, dtype):
        import jax

        from benchmark.reference import ouro as ref

        key = ("reference", tuple(sorted(self.cfg.items())), self.block,
               str(dtype))
        if key not in _JITS:
            cfg, block = self.cfg, self.block
            _JITS[key] = jax.jit(lambda q, row: ref.loss_and_grads(
                q, row, cfg, block, True, dtype, with_states=True))
        return _JITS[key]

    def __call__(self, p, rows, dtype, states=True) -> dict:
        import jax
        import jax.numpy as jnp

        t0 = time.perf_counter()
        run, batch = self._run(dtype), rows.shape[0]
        loss, grads, hs, mass = 0.0, None, [], []
        for i in range(batch):
            value, aux, g = run(p, rows[i:i + 1])
            g = [np.asarray(a, np.float32) / batch for a in jax.tree.leaves(g)]
            loss += float(value) / batch
            grads = g if grads is None else [a + b for a, b in zip(grads, g)]
            if states:
                hs.append(aux["hidden"].astype(jnp.float32))
                mass.append(aux["mass"].astype(jnp.float32))
        out = {"loss": loss, "grads": grads}
        if states:
            out.update(hs=jnp.concatenate(hs, axis=1),
                       mass=jnp.concatenate(mass, axis=1))
        self.seconds += time.perf_counter() - t0
        return out

    def epoch(self, theta0, treedef, batches, hyper, loss_and_grads, first=None):
        """An epoch from the leaves ``theta0`` through the reference's AdamW
        on the host, batch after batch: ``loss_and_grads(params, batch)``
        gives {"loss", "grads": leaves} (``first``: the first batch's, taken
        already). Returns (the leaves after the epoch, each step's loss)."""
        import jax
        import jax.numpy as jnp

        from benchmark.reference import ouro as ref

        leaves, state, losses, out = theta0, ref.adamw_init(theta0), [], first
        for i, rows in enumerate(batches):
            if out is None:
                p = jax.tree.unflatten(treedef,
                                       [jnp.asarray(a) for a in leaves])
                out = loss_and_grads(p, rows)
                del p
            t0 = time.perf_counter()  # the reference's runs count themselves
            losses.append(out["loss"])
            leaves, state = ref.adamw_step(
                leaves, out["grads"], state, hyper["learning_rate"],
                hyper["b1"], hyper["b2"], hyper["weight_decay"])
            self.seconds += time.perf_counter() - t0
            out = None
        return leaves, losses


def step_gaps(got, loss: float, a: dict, path: str = "ref"):
    """An epoch's outcome (the parameters' leaves ``got``, its mean training
    loss) against a replayed epoch of ``a`` from ``theta0`` (``path``
    "ref": ``theta_ref``, ``ref_epoch_loss``; "own": ``theta_own``,
    ``own_epoch_loss``): ({gap: value}, each parameter's ``change_rel``,
    finiteness)."""
    import jax
    import jax.numpy as jnp

    if "step_parts" not in _JITS:
        _JITS["step_parts"] = jax.jit(lambda e, z, r: jnp.stack(
            [(((e - z) - (r - z)) ** 2).sum(), ((r - z) ** 2).sum(),
             ((e - z) ** 2).sum()]))
    parts = _JITS["step_parts"]
    sq = np.array([np.asarray(parts(e, z, r), np.float64)
                   for e, z, r in zip(got, a["theta0"], a[f"theta_{path}"])])
    finite = bool(np.isfinite(sq).all() and np.isfinite(loss))
    far_of, norm_of = [], []
    for idx in a["groups"]:
        d, r, e = sq[idx].sum(axis=0)
        far_of.append(np.sqrt(d / max(r, 1e-40)))
        norm_of.append(abs(np.sqrt(e / max(r, 1e-40)) - 1.0))
    return ({"loss_abs": abs(loss - a[f"{path}_epoch_loss"]),
             "change_rel": float(np.max(far_of)),
             "change_norm": float(np.max(norm_of))},
            np.asarray(far_of), finite)


def _gaps(module, run, params, x, ref_out, groups):
    """The program's objective on batch ``x`` against the reference's
    outputs: {gap name: value}, per-parameter gradient ratios, finiteness."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import ouro as ref

    (loss, aux), grads = run(params, x)

    @jax.jit
    def exit_gap(p, h, h_ref):
        z = module.apply(p, h, method="head")
        z_ref = ref.logits_of(p, h_ref)
        return (jnp.abs(z - z_ref).max() / jnp.abs(z_ref).max(),
                jnp.isfinite(z).all())

    logits_rel, finite = 0.0, bool(jnp.isfinite(loss))
    for t in range(aux["hidden"].shape[0]):
        gap, fin = exit_gap(params, aux["hidden"][t], ref_out["hs"][t])
        logits_rel, finite = max(logits_rel, float(gap)), finite and bool(fin)
    mass = float(jnp.abs(aux["mass"] - ref_out["mass"]).max())
    per_leaf = group_ratios(jax.tree.leaves(grads), ref_out["grads"], groups)
    return ({"loss_abs": abs(float(loss) - ref_out["loss"]),
             "logits_rel": logits_rel, "mass_abs": mass,
             "grads_rel": float(per_leaf.max())},
            per_leaf, finite)


def check_objective(ctx, module, est, train: np.ndarray, held: np.ndarray,
                    seed: int, lower_reading: bool = False) -> dict:
    """Part (a), first half. On the FIRST batch the fit will train on (the
    estimator's own ``epoch_order``), with the parameters it starts from:
    the objective the step program differentiates against the reference,
    ``matched`` and ``as_run``. Then the epoch replayed through the
    reference's AdamW, batch after batch in that order, once from the
    reference's gradients and once from the program's own objective's, for
    ``check_step`` to hold the estimator's epoch program to. Also the
    held-out loss with the initial parameters, for part (c)."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import ouro as ref

    tr = ctx.traffic
    batch = int(tr["batch"])
    order = np.asarray(est.epoch_order(0, len(train)))
    if sorted(order.tolist()) != list(range(len(train))):
        raise RuntimeError(f"epoch_order(0) is not a permutation: {order}")
    steps = len(train) // batch
    batches = [jnp.asarray(train[order[i * batch:(i + 1) * batch]])
               for i in range(steps)]
    x = batches[0]
    cfg = reference_cfg(ctx)
    block = int(tr["reference_token_block"])
    params = jax.jit(
        lambda r: module.init(r, x, None, method="loss")
    )(jax.random.PRNGKey(seed % (2 ** 31)))
    treedef = jax.tree.structure(params)
    leaf_names, groups = grad_groups(params)
    reference = Reference(cfg, block)

    ref_out = reference(params, x, jnp.float32)
    run_as = _objective(module)
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
                    variant, _objective(variant), params, x, ref_out, groups)
        else:
            gaps, per_leaf, finite = _gaps(
                module, run_as, params, x, ref_out, groups)
        limits = tol[mode]
        held_ = finite and all(gaps[k] <= limits[k] for k in GAPS)
        ok = ok and held_
        far = np.argsort(-per_leaf)[:3]
        ctx.say(f"part (a) objective, {mode}, the fit's first batch of "
                f"{batch} x {x.shape[1] - 1} tokens, {module.loop_steps} "
                "exits out of the scanned loop: "
                + ", ".join(f"{k} {gaps[k]:.3g} (limit {limits[k]})"
                            for k in GAPS)
                + f": {'ok' if held_ else 'FAIL'}; gradients farthest (L2, "
                "relative): " + ", ".join(
                    f"{leaf_names[i]} {per_leaf[i]:.3g}" for i in far))
        worst.update({f"{mode}.{k}": v for k, v in gaps.items()})
    (initial, _), _ = run_as(params, jnp.asarray(held[:batch]))
    initial = float(initial)
    if lower_reading:
        # the second reading: the reference itself, computed in bf16 from
        # end to end (matmuls, activations, logits, loss), held to the
        # as_run limits. It has to be refused.
        low = reference(params, x, jnp.bfloat16)

        @jax.jit
        def low_exit_gap(p, h, h0):
            z = ref.logits_of(p, h.astype(jnp.bfloat16), jnp.bfloat16)
            z0 = ref.logits_of(p, h0)
            return jnp.abs(z.astype(jnp.float32) - z0).max() / jnp.abs(z0).max()

        gaps = {
            "loss_abs": abs(low["loss"] - ref_out["loss"]),
            "logits_rel": max(float(low_exit_gap(params, h, h0))
                              for h, h0 in zip(low["hs"], ref_out["hs"])),
            "mass_abs": float(jnp.abs(low["mass"] - ref_out["mass"]).max()),
            "grads_rel": float(group_ratios(
                low["grads"], ref_out["grads"], groups).max()),
        }
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
    theta_ref, losses = reference.epoch(
        theta0, treedef, batches, hyper,
        lambda p, rows: reference(p, rows, jnp.float32, states=False), ref_out)
    del ref_out

    def own(p, rows):
        # the program's own objective (as run) in the reference's place
        (loss, _), grads = run_as(p, rows)
        return {"loss": float(loss), "grads": [
            np.asarray(g, np.float32) for g in jax.tree.leaves(grads)]}

    theta_own, losses_own = reference.epoch(theta0, treedef, batches, hyper, own)
    del run_as
    a = {"groups": groups, "leaf_names": leaf_names, "treedef": treedef,
         "theta0": theta0,
         "theta_ref": theta_ref, "ref_epoch_loss": float(np.mean(losses)),
         "ref_step_losses": [float(v) for v in losses],
         "theta_own": theta_own, "own_epoch_loss": float(np.mean(losses_own)),
         "own_step_losses": [float(v) for v in losses_own], "order": order}
    if lower_reading:
        # the step's second reading: the bf16 reference's own epoch
        theta_low, losses_low = reference.epoch(
            theta0, treedef, batches, hyper,
            lambda p, rows: reference(p, rows, jnp.bfloat16, states=False), low)
        del low
        gaps, _, _ = step_gaps(theta_low, float(np.mean(losses_low)), a)
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


def check_step(ctx, module, train_df, held_df, seed: int, a: dict) -> dict:
    """Part (a), second half: ONE epoch of a fit through the estimator's own
    compiled epoch program (``make_train_step`` inside the scan runner,
    donation, the optimizer the configuration names), from the seeded
    parameters on the cell's batches, against the two replays: the epoch's
    mean training loss and each parameter's change, each gap at the limit
    the mix gives it for that replay."""
    import jax
    import jax.numpy as jnp

    est = make_estimator(ctx, module, seed, num_epochs=1)
    history = est.fit_on_etl(train_df, held_df)
    loss = float(history[0]["train_loss"])
    got = jax.tree.leaves(est.get_model().params)
    tol = ctx.traffic["arith_tolerance"]
    ok, out = True, {"step.loss": loss}
    for path, key, what in (
            ("own", "step_own", "the program's own objective's gradients"),
            ("ref", "step", "the reference's gradients")):
        gaps, far_of, finite = step_gaps(got, loss, a, path)
        limits = tol[key]  # a gap with no limit is printed, not judged
        held_ = finite and all(gaps[k] <= limits[k] for k in limits)
        ok = ok and held_
        far = np.argsort(-far_of)[:3]
        ctx.say(f"part (a) step, as run: one epoch of "
                f"{est.fit_stats_['steps']} steps of the estimator's own "
                f"epoch program in the order {a['order'].tolist()} against "
                f"{what} through the reference's AdamW: "
                + ", ".join(f"{k} {gaps[k]:.3g} "
                            f"({'limit ' + str(limits[k]) if k in limits else 'not judged'})"
                            for k in STEP_GAPS)
                + f": {'ok' if held_ else 'FAIL'}; mean training loss "
                f"{loss:.5f} (replayed {a[path + '_epoch_loss']:.5f}); "
                "changes farthest (L2, relative): " + ", ".join(
                    f"{a['leaf_names'][i]} {far_of[i]:.3g}" for i in far))
        out.update({f"{key}.{k}": v for k, v in gaps.items()})
    if est.fit_stats_["row_update"]["params"]:
        raise RuntimeError("AdamW and a model without row_gathers must keep "
                           f"the dense step: {est.fit_stats_['row_update']}")
    est.clear_staging_cache()
    return {"ok": ok, **out}


def make_estimator(ctx, module, seed: int, num_epochs: int):
    from raydp_tpu import models
    from raydp_tpu.estimator import JaxEstimator

    optimizer = getattr(models, ctx.config["model"]["optimizer"])(
        **ctx.config["model"]["adamw"])
    return JaxEstimator(
        model=module, optimizer=optimizer, loss=ctx.config["model"]["loss"],
        feature_columns=[tokens.TOKENS], feature_dtype=np.int32,
        label_column=None, batch_size=int(ctx.traffic["batch"]),
        num_epochs=num_epochs, seed=seed % (2 ** 31),
        streaming=bool(ctx.traffic["streaming"]))


def warm_up(ctx, module, train, held, seed: int) -> dict:
    """A fit of a FIXED number of epochs through the cell's own runner: it
    compiles every shape the window uses, and part (c) reads its last
    held-out loss."""
    epochs = int(ctx.traffic["warmup_epochs"])
    est = make_estimator(ctx, module, seed, epochs)
    t0 = time.perf_counter()
    history = est.fit_on_etl(train, held)
    ctx.say_time(f"warm-up fit ({epochs} epoch(s), compile "
                 f"{est.compile_seconds_:.1f} s inside)", time.perf_counter() - t0)
    ctx.say("warm-up fit, held-out loss after each epoch: " + ", ".join(
        f"{float(rec['eval_loss']):.4f}" for rec in history))
    ctx.say("warm-up fit, last evaluation, per exit: loss " + ", ".join(
        f"{v:.4f}" for v in history[-1]["eval_exit_loss"]) + "; mass "
        + ", ".join(f"{v:.4f}" for v in history[-1]["eval_exit_mass"]))
    stats_ = est.fit_stats_
    est.clear_staging_cache()
    return {"fitted": float(history[-1]["eval_loss"]),
            "peak_source": stats_.get("peak_source"),
            "flops_per_step_program": stats_.get("flops_per_step"),
            "compile_s": est.compile_seconds_}


def fit_trains(ctx, initial_loss: float, fitted: float) -> dict:
    """Part (c): held-out loss after the fixed warm-up fit against the loss
    with the initial parameters."""
    margin = initial_loss - fitted
    need = float(ctx.traffic["min_learning_margin"])
    ok = bool(np.isfinite(fitted) and margin >= need)
    ctx.say(f"part (c) the fit trains: held-out loss {initial_loss:.4f} with "
            f"the initial parameters, {fitted:.4f} after the warm-up fit; "
            f"margin {margin:.4f} (needs >= {need}): {'ok' if ok else 'FAIL'}")
    return {"ok": ok, "margin": margin}


def checks(ctx, module, train, held, train_rows, held_rows, seed: int,
           lower_reading: bool = False):
    """Parts (a) and (c) around the warm-up fit, in the order that keeps
    the estimator's compile where a job pays it: the objective against the
    reference (its programs are the check's own), the warm-up fit (compiles
    the epoch program), then one epoch of that program against the
    reference's epoch. Returns (a, c, the warm-up's facts, the seconds the
    check alone took: everything here but the warm-up fit)."""
    t0 = time.perf_counter()
    a = check_objective(ctx, module, make_estimator(ctx, module, seed, 1),
                        train_rows, held_rows, seed, lower_reading)
    check_s = time.perf_counter() - t0
    gc.collect()
    warm = warm_up(ctx, module, train, held, seed)
    t0 = time.perf_counter()
    step = check_step(ctx, module, train, held, seed, a)
    for key in ("theta0", "theta_ref", "theta_own"):
        del a[key]
    a.update(step, ok=a["ok"] and step["ok"])
    c = fit_trains(ctx, a["initial_held_out_loss"], warm["fitted"])
    check_s += time.perf_counter() - t0
    ctx.say_time("part (a) in all (no job runs it: outside set-up)", check_s)
    return a, c, warm, check_s


def _check_seed(ctx, seed: int) -> None:
    ctx.claim_device()
    c, tr = ctx.config, ctx.traffic
    session = start_etl(ctx)
    module = make_model(ctx)
    table, raw = tokens.raw_frame(
        seed, int(tr["rows"]), int(tr["seq_len"]), c["vocab_size"],
        float(tr["zipf_a"]), float(tr["bigram_tilt"]))
    train, held, _ = preprocess(ctx, session, table, seed)
    b, rows = check_data(ctx, raw, train, held)
    a, cc, warm, _ = checks(ctx, module, train, held, rows["train"],
                            rows["held_out"], seed, lower_reading=True)
    ctx.say(
        f"seed {seed}: (a) " + "; ".join(
            f"{mode}: " + " ".join(f"{k} {a[f'{mode}.{k}']:.3g}" for k in GAPS)
            for mode in ("as_run", "matched", "bf16_reference"))
        + "".join(
            f"; {key}: " + " ".join(f"{k} {a[f'{key}.{k}']:.3g}" for k in STEP_GAPS)
            for key in ("step_own", "step"))
        + f"; bf16 reference refused={a['bf16_reference.refused']} | "
        f"(b) exact={b['ok']} | (c) held-out "
        f"{a['initial_held_out_loss']:.4f} -> {warm['fitted']:.4f} "
        f"margin {cc['margin']:.4f}")
    ctx.write(ctx.phase, {
        "correct": {f"seed_{seed}": a["ok"] and b["ok"] and cc["ok"]},
        "device": ctx.device})


# -- the run --------------------------------------------------------------


def _leave_after(ctx, body) -> None:
    """Run a phase's body and leave the process. Whatever the phase started
    is stopped here, on every path: executors and head first; the window's
    fit thread dies with the process."""
    code = 1
    try:
        body(ctx)
        code = 0
    except BaseException as exc:  # noqa: BLE001 - reported, then we leave
        import traceback

        traceback.print_exc()
        ctx.say(f"phase {ctx.phase} failed: {exc!r}")
    try:
        stop_etl()
    finally:
        os._exit(code)


def _fit(ctx) -> None:
    dev = ctx.claim_device()
    import jax

    from raydp_tpu import obs

    c, tr = ctx.config, ctx.traffic
    batch, train_rows = int(tr["batch"]), int(tr["train_rows"])
    seq_len, vocab = int(tr["seq_len"]), int(c["vocab_size"])
    steps_per_epoch = train_rows // batch
    seed = ctx.seed

    t = time.perf_counter()
    table, raw = tokens.raw_frame(seed, int(tr["rows"]), seq_len, vocab,
                                  float(tr["zipf_a"]), float(tr["bigram_tilt"]))
    ctx.say_time(f"{table.num_rows} sequences of {seq_len}+1 ids from seed "
                 f"{seed}", time.perf_counter() - t)
    session = start_etl(ctx)
    train, held, query_s = preprocess(ctx, session, table, seed)
    ctx.say_time("the query (last_query_stats)", query_s)
    del table
    part_b, rows = check_data(ctx, raw, train, held)
    del raw
    module = make_model(ctx)
    part_a, part_c, warm, check_s = checks(
        ctx, module, train, held, rows["train"], rows["held_out"], seed)
    if not ctx.rehearsal and warm["peak_source"] != "tpu-table":
        raise RuntimeError(f"estimator's peak_source is "
                           f"{warm['peak_source']!r}, not 'tpu-table'")
    ctx.say(f"device memory peak after the checks and the warm-up fit: "
            f"{ctx.memory_peak_bytes()} bytes")

    est = make_estimator(ctx, module, seed, num_epochs=1_000_000)
    failure = []
    # set-up's garbage (GBs of reference parameters among it) is collected
    # here, not by a full collection that lands inside the window: one run
    # of seven lost 0.1 s of its 19.2-s window to one stall (PERF.md)
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
    # part (a) is the benchmark's own work (the reference, two programs no
    # job runs, one epoch replayed): a job's set-up is what is left
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

    summary = None
    if ctx.trace and not ctx.rehearsal:
        summary = xplane.reduce_trace(xplane.find_xplane(trace_dir))
        xplane.keep_copy(trace_dir, ctx)
    result = {
        "attempted": len(records), "failed": 0,
        "correct": {"a_arithmetic": part_a["ok"], "b_data": part_b["ok"],
                    "c_fit_trains": part_c["ok"], "d_window": part_d},
        "device": {**dev, "memory_peak_bytes": ctx.memory_peak_bytes()},
    }
    end_to_end = {"fit_samples_per_s": rate["rate"], "setup_s": setup_s}
    if not ctx.rehearsal:
        ctx.say(f"window: {rate['work']:.0f} sequences between "
                f"{rate['fences']} fences over {rate['elapsed_s']:.3f} s = "
                f"{rate['rate']:.4f} sequences/s = "
                f"{rate['rate'] * seq_len:.1f} tokens/s; set-up {setup_s:.2f} "
                f"s (part (a)'s {check_s:.2f} s left out); on {dev['kind']} "
                f"x{dev['count']}")
    if ctx.trace and not ctx.rehearsal:
        peaks = peaks_for(dev["kind"])
        flops = lm_costs.looplm_step_flops(
            batch, seq_len, c["hidden_size"], c["intermediate_size"],
            c["num_hidden_layers"], c["total_ut_steps"], vocab)
        snap = obs.metrics.snapshot()
        ctx.say("FLOPs of a step: from shapes (lm_costs) "
                + ", ".join(f"{k} {v:.4g}" for k, v in flops.items())
                + f"; the program's own count (fit_facts' flops_per_row x "
                f"batch) {warm['flops_per_step_program']}; program gauges "
                + ", ".join(f"{k}={snap[k]['value']:.6g}" for k in sorted(snap)
                            if k.startswith(("model.", "estimator.eval.",
                                             "estimator.tokens"))))
        heads, head_dim = c["num_attention_heads"], c["head_dim"]
        sources = {
            "values": {
                "etl_query_s": query_s,
                "steps_in_trace": steps_in_trace,
                "vocab_size": vocab,
                "model_flops_utilization_pct": 100.0 * flops["total"]
                * rate["rate"] / batch / peaks["flops_per_s"],
            },
            "trace": summary, "peaks": peaks,
            "kernels": {
                "flash_fwd": {"cost": lm_costs.flash_fwd(
                    batch, heads, seq_len, head_dim, 2)},
                "flash_bwd": {"cost": lm_costs.flash_bwd(
                    batch, heads, seq_len, head_dim, 2)},
            },
        }
        result["metrics"] = layers.read_all(ctx.cell, sources)
        result["device"].update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    elif not ctx.rehearsal:
        result["metrics"] = metric_dict(ctx.cell, end_to_end)
    ctx.write(ctx.phase, result)
