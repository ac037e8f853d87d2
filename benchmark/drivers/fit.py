"""Traffic kind ``fit``: one ETL -> ``JaxEstimator.fit_on_etl`` job on
Criteo-shaped rows, streamed or device-resident (the mix's ``streaming``).

One phase, one process (it holds the chip from its first array on):

set-up   raw rows from the seed -> ``init_etl`` -> the preprocessing query
         (``F.log1p`` x dense, ``F.hash(col, vocab)`` x categorical,
         ``random_split``) -> correctness parts (a) arithmetic, (b) data ->
         a warm-up fit of a fixed number of steps through the cell's own
         runner, which compiles the cell's shapes and decides part (c).
window   ONE ``fit_on_etl(train, held_out)`` with more epochs than any window
         holds, in a thread. Each epoch ends in the estimator's own held-out
         evaluation, whose loss fetch drains the device: that is a fence.
         The main thread watches ``estimator.history`` grow and stamps each
         fence. The rate is the samples between the first fence at or after
         the window opens and the last one before it closes, over the time
         between those two fences. The window opens at the first fence of
         this fit (its programs come from the compile cache by then). When
         the window closes the process leaves; the fit is not waited for.

``correct`` never looks at the clock: (a) program loss/logits/gradients vs
``benchmark/reference/dlrm.py`` on batches the check feeds, as the program
runs and again at the reference's matmul precision; (b) ETL output vs
a numpy/pandas recomputation by order-independent checksums, exact row
counts; (c) held-out loss after the fixed-step warm-up fit is lower than
with the initial parameters by the mix's ``min_learning_margin``; (d) every
loss the window's fit reported is finite and every epoch it counted has
exactly train_rows // batch steps.
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from benchmark.harness import costs, criteo, layers, stats, xplane
from benchmark.harness.child import metric_dict
from benchmark.harness.peaks import peaks_for

# the step profiler's phases that bracket fenced host work; its compute and
# sync phases bracket asynchronous dispatches and are not read (PERF.md)
STEP_HISTOGRAMS = ("estimator.step.ingest_ms", "estimator.step.h2d_ms")


def phases(trace: bool):
    return ["fit"]


def run_phase(ctx) -> None:
    {"fit": _fit, "check_seeds": _check_seeds}[ctx.phase](ctx)


def check_phases():
    """``run.py --check-seeds``: parts (a), (b), (c) alone, every seed in one
    process and one compilation (they need no window)."""
    return ["check_seeds"]


# -- set-up pieces (the run and --check-seeds share them) --------------------


def columns(model: dict):
    dense = [f"i{i}" for i in range(model["num_dense"])]
    cats = [f"c{j}" for j in range(len(model["vocab_sizes"]))]
    return dense, cats


def start_etl(ctx):
    import raydp_tpu

    cores = max(1, min(4, ((os.cpu_count() or 1) - 2) // 4))
    executors = int(ctx.traffic.get("executors", 2))
    session = raydp_tpu.init_etl(
        "benchmark", num_executors=executors, executor_cores=cores,
        executor_memory="2G")
    ctx.say(f"init_etl: {executors} executors x {cores} core(s)")
    return session, executors * cores


def preprocess(ctx, session, table, parallelism: int, seed: int):
    """The preprocessing query, through the program's entry points. Returns
    (train_df, held_out_df, seconds the query took by the program's own
    ``last_query_stats``)."""
    from raydp_tpu.etl import functions as F

    model = ctx.config["model"]
    dense, cats = columns(model)
    df = session.from_arrow(table, num_partitions=2 * parallelism)
    for col in dense:
        df = df.with_column(col, F.log1p(F.col(col)).cast("float32"))
    for col, vocab in zip(cats, model["vocab_sizes"]):
        df = df.with_column(col, F.hash(col, vocab).cast("int32"))
    train_df, test_df = df.random_split([0.9, 0.1], seed=seed % (2 ** 31))
    return train_df, test_df, float(session.last_query_stats["seconds"])


def _bits_sum(a: np.ndarray) -> int:
    """Order-independent, exact: the sum of the values' bit patterns."""
    return int(a.view(np.uint32).astype(np.uint64).sum(dtype=np.uint64))


def check_data(ctx, raw: dict, train_df, test_df) -> dict:
    """Part (b): the ETL's output against a recomputation from the raw
    columns, by checksums that do not depend on row order."""
    import pandas as pd
    from concurrent.futures import ThreadPoolExecutor

    model = ctx.config["model"]
    dense, cats = columns(model)
    out = [train_df.to_arrow(), test_df.to_arrow()]
    rows = sum(t.num_rows for t in out)
    rows_total = len(raw["label"])
    bad = []
    if rows != rows_total:
        bad.append(f"rows {rows} != {rows_total}")

    def got(col):
        return np.concatenate(
            [t.column(col).to_numpy(zero_copy_only=False) for t in out])

    worst_dense = 0.0
    for col in dense:
        want = np.log1p(raw[col].astype(np.float64)).astype(np.float32)
        have = got(col)
        if have.dtype != np.float32:
            bad.append(f"{col} is {have.dtype}")
            continue
        # arrow's and numpy's log1p may differ in the last float32 place, so
        # this column is held to a relative 1e-6 on two moments in float64
        for power in (1, 2):
            w = float((want.astype(np.float64) ** power).sum())
            h = float((have.astype(np.float64) ** power).sum())
            rel = abs(w - h) / max(abs(w), 1e-30)
            worst_dense = max(worst_dense, rel)
            if rel > 1e-6:
                bad.append(f"{col} moment {power}: {h} vs {w}")

    def cat_ok(item):
        j, col = item
        vocab = np.uint64(model["vocab_sizes"][j])
        strings = criteo.hex_strings(raw[col]).to_pandas()
        want = (pd.util.hash_array(np.asarray(strings)).astype(np.uint64)
                % vocab).astype(np.int64)
        have = got(col).astype(np.int64)
        same = (len(have) == len(want)
                and int(have.sum()) == int(want.sum())
                and int((have * have % 1000003).sum())
                == int((want * want % 1000003).sum())
                and int(have.min()) >= 0 and int(have.max()) < int(vocab))
        return None if same else f"{col}: hashed ids differ"

    with ThreadPoolExecutor(max_workers=4) as pool:
        bad += [b for b in pool.map(cat_ok, enumerate(cats)) if b]
    label_ok = _bits_sum(got("label").astype(np.float32)) == _bits_sum(
        raw["label"])
    if not label_ok:
        bad.append("label column differs")
    ctx.say(f"part (b) data: rows {rows}/{rows_total}, dense moments worst "
            f"relative difference {worst_dense:.3g} (limit 1e-6), "
            f"{len(cats)} hashed columns and the label by exact checksums: "
            f"{'ok' if not bad else 'FAIL ' + '; '.join(bad)}")
    return {"ok": not bad, "dense_rel": worst_dense}


def make_model(ctx):
    from raydp_tpu.models import DLRM

    m = ctx.config["model"]
    return DLRM(
        vocab_sizes=tuple(m["vocab_sizes"]), num_dense=m["num_dense"],
        embed_dim=m["embed_dim"], bottom_mlp=tuple(m["bottom_mlp"]),
        top_mlp=tuple(m["top_mlp"]), use_pallas_interaction=True)


def held_out_arrays(ctx, held_df):
    """(dense [N,13] f32, ids [N,26] i32, label [N] f32) of the held-out
    frame the fits evaluate on, in the frame's own order."""
    dense, cats = columns(ctx.config["model"])
    t = held_df.to_arrow()

    def col(c):
        return t.column(c).to_numpy(zero_copy_only=False)

    return (np.stack([col(c) for c in dense], 1).astype(np.float32),
            np.stack([col(c) for c in cats], 1).astype(np.int32),
            col("label").astype(np.float32))


def check_arithmetic(ctx, module, held, seed: int) -> dict:
    """Part (a) and the initial held-out loss for part (c): the program's
    loss (``_LOSSES["bce"]`` over ``module.apply``, Pallas interaction
    compiled) against the plain reference, on ``arith_batches`` batches of
    the cell's real shapes, with the parameters the estimator starts from."""
    import jax
    import jax.numpy as jnp

    from benchmark.reference import dlrm as ref
    from raydp_tpu.estimator.jax_estimator import _LOSSES

    m, tr = ctx.config["model"], ctx.traffic
    batch, n_batches = int(tr["batch"]), int(tr["arith_batches"])
    dense, ids, label = held
    nb, nt = len(m["bottom_mlp"]), len(m["top_mlp"])
    sample = (jnp.asarray(dense[:batch]), jnp.asarray(ids[:batch]))
    params = jax.jit(module.init)(jax.random.PRNGKey(seed % (2 ** 31)), sample)

    def program(p, x, y):
        def loss(p):
            logits = module.apply(p, x)
            return _LOSSES["bce"](logits, y), logits

        return jax.value_and_grad(loss, has_aux=True)(p)

    def comparison(precision):
        """The program's loss and gradients against the reference's, the
        program traced under ``precision`` (None: as it runs)."""

        @jax.jit
        def compare(p, d, i, y):
            with jax.default_matmul_precision(precision):
                (l1, z1), g1 = program(p, (d, i), y)
            l2, z2, g2 = ref.loss_and_grads(p, d, i, y, nb, nt)
            # per parameter: the gradient's distance from the reference's
            # in the L2 norm, over the reference's norm
            rel = jax.tree.map(
                lambda a, b: jnp.sqrt(((a - b) ** 2).sum()) / jnp.maximum(
                    jnp.sqrt((b ** 2).sum()), 1e-20), g1, g2)
            return (jnp.abs(l1 - l2),
                    jnp.abs(z1 - z2).max() / jnp.abs(z2).max(),
                    jnp.stack(jax.tree.leaves(rel)),
                    jnp.isfinite(l1) & jnp.isfinite(z1).all())

        return compare

    if not ctx.rehearsal:
        text = jax.jit(program).lower(
            params, sample, jnp.asarray(label[:batch])).as_text()
        if "tpu_custom_call" not in text:
            raise RuntimeError("no Mosaic custom call in the program's loss: "
                               "a stand-in ran in the interaction kernel's place")
    # as_run: the program as it runs (float32 matmuls as single bf16 passes
    # on the MXU). matched: the program traced at the reference's precision,
    # where only the order of summation is left to differ, held far tighter:
    # a bf16 model or a wrong backward pass cannot hide in it.
    tol = tr["arith_tolerance"]
    leaf_names = [jax.tree_util.keystr(k) for k, _ in
                  jax.tree_util.tree_leaves_with_path(params)]
    finite, ok, worst = True, True, {}
    for mode, precision in (("as_run", None), ("matched", "highest")):
        compare = comparison(precision)
        gaps = {"loss_abs": 0.0, "logits_rel": 0.0, "grads_rel": 0.0}
        per_leaf = np.zeros(len(leaf_names))
        for b in range(n_batches):
            sl = slice(b * batch, (b + 1) * batch)
            dl, dz, dg, fin = compare(params, jnp.asarray(dense[sl]),
                                      jnp.asarray(ids[sl]),
                                      jnp.asarray(label[sl]))
            gaps["loss_abs"] = max(gaps["loss_abs"], float(dl))
            gaps["logits_rel"] = max(gaps["logits_rel"], float(dz))
            per_leaf = np.maximum(per_leaf, np.asarray(dg))
            finite = finite and bool(fin)
        gaps["grads_rel"] = float(per_leaf.max())
        limits = tol[mode]
        held = all(gaps[k] <= limits[k] for k in gaps)
        ok = ok and held
        far = np.argsort(-per_leaf)[:3]
        ctx.say(f"part (a) arithmetic, {mode}, {n_batches} batches of {batch}: "
                + ", ".join(f"{k} {gaps[k]:.3g} (limit {limits[k]})"
                            for k in gaps)
                + f": {'ok' if held else 'FAIL'}; gradients farthest (L2, "
                "relative): " + ", ".join(
                    f"{leaf_names[i]} {per_leaf[i]:.3g}" for i in far))
        worst.update({f"{mode}.{k}": v for k, v in gaps.items()})
    ok = ok and finite

    loss_fn = jax.jit(lambda p, d, i, y: _LOSSES["bce"](module.apply(p, (d, i)), y))
    n = (len(label) // batch) * batch
    initial = float(np.mean([
        float(loss_fn(params, jnp.asarray(dense[s:s + batch]),
                      jnp.asarray(ids[s:s + batch]),
                      jnp.asarray(label[s:s + batch])))
        for s in range(0, n, batch)]))
    del params
    return {"ok": ok, "initial_held_out_loss": initial, **worst}


def make_estimator(ctx, module, seed: int, num_epochs: int):
    from raydp_tpu import models
    from raydp_tpu.estimator import JaxEstimator

    dense, cats = columns(ctx.config["model"])
    # the name of an optimizer factory of raydp_tpu.models, called with its
    # defaults, or else a name the estimator knows
    optimizer = ctx.config["model"]["optimizer"]
    if hasattr(models, optimizer):
        optimizer = getattr(models, optimizer)()
    return JaxEstimator(
        model=module, optimizer=optimizer, loss="bce",
        feature_columns=dense + cats, categorical_columns=cats,
        label_column="label", batch_size=int(ctx.traffic["batch"]),
        learning_rate=float(ctx.config["model"].get("learning_rate", 1e-3)),
        num_epochs=num_epochs, seed=seed % (2 ** 31),
        streaming=bool(ctx.traffic["streaming"]))


def warm_up(ctx, module, train, held, seed: int, initial_loss: float) -> dict:
    """Part (c): a fit of a FIXED number of steps through the cell's own
    runner (it also compiles every shape the window uses)."""
    epochs = int(ctx.traffic["warmup_epochs"])
    est = make_estimator(ctx, module, seed, epochs)
    t0 = time.perf_counter()
    history = est.fit_on_etl(train, held)
    ctx.say_time(f"warm-up fit ({epochs} epoch(s), compile "
                 f"{est.compile_seconds_:.1f} s inside)", time.perf_counter() - t0)
    fitted = float(history[-1]["eval_loss"])
    ctx.say("warm-up fit, held-out loss after each epoch: " + ", ".join(
        f"{float(rec['eval_loss']):.5f}" for rec in history))
    margin = initial_loss - fitted
    need = float(ctx.traffic["min_learning_margin"])
    ok = bool(np.isfinite(fitted) and margin >= need)
    ctx.say(f"part (c) the fit trains: held-out loss {initial_loss:.5f} with "
            f"the initial parameters, {fitted:.5f} after the warm-up fit; "
            f"margin {margin:.5f} (needs >= {need}): {'ok' if ok else 'FAIL'}")
    stats_ = getattr(est, "fit_stats_", {}) or {}
    return {"ok": ok, "margin": margin, "fitted": fitted,
            "peak_source": stats_.get("peak_source"),
            "compile_s": est.compile_seconds_}


def _check_seeds(ctx) -> None:
    ctx.claim_device()
    import raydp_tpu
    from raydp_tpu.cluster import api as cluster

    m, tr = ctx.config["model"], ctx.traffic
    session, parallelism = start_etl(ctx)
    module = make_model(ctx)
    rows, all_ok = [], True
    for seed in ctx.check_seeds:
        table, raw = criteo.raw_frame(seed, int(tr["rows"]), m["num_dense"],
                                      m["vocab_sizes"], float(tr["zipf_a"]))
        train_df, test_df, _ = preprocess(ctx, session, table, parallelism, seed)
        b = check_data(ctx, raw, train_df, test_df)
        del table, raw
        train = train_df.limit(int(tr["train_rows"]))
        held = test_df.limit(int(tr["held_out_rows"]))
        a = check_arithmetic(ctx, module, held_out_arrays(ctx, held), seed)
        c = warm_up(ctx, module, train, held, seed, a["initial_held_out_loss"])
        all_ok = all_ok and a["ok"] and b["ok"] and c["ok"]
        rows.append(
            f"seed {seed}: (a) as run: loss {a['as_run.loss_abs']:.3g} "
            f"logits {a['as_run.logits_rel']:.3g} grads "
            f"{a['as_run.grads_rel']:.3g}; matched: loss "
            f"{a['matched.loss_abs']:.3g} logits "
            f"{a['matched.logits_rel']:.3g} grads "
            f"{a['matched.grads_rel']:.3g} | (b) dense "
            f"{b['dense_rel']:.3g} hashed+label+rows exact={b['ok']} | (c) "
            f"held-out {a['initial_held_out_loss']:.5f} -> {c['fitted']:.5f} "
            f"margin {c['margin']:.5f}")
    for row in rows:
        ctx.say(row)
    ctx.write(ctx.phase, {"correct": {"every_seed": all_ok},
                          "device": ctx.device})
    try:
        raydp_tpu.stop_etl()
        cluster.shutdown()
    finally:
        os._exit(0)


# -- the run --------------------------------------------------------------


def _fit(ctx) -> None:
    dev = ctx.claim_device()
    import jax

    import raydp_tpu
    from raydp_tpu.cluster import api as cluster

    m, tr = ctx.config["model"], ctx.traffic
    batch, train_rows = int(tr["batch"]), int(tr["train_rows"])
    steps_per_epoch = train_rows // batch
    seed = ctx.seed

    t = time.perf_counter()
    table, raw = criteo.raw_frame(seed, int(tr["rows"]), m["num_dense"],
                                  m["vocab_sizes"], float(tr["zipf_a"]))
    ctx.say_time(f"raw frame of {table.num_rows} rows from seed {seed}",
                 time.perf_counter() - t)
    session, parallelism = start_etl(ctx)
    train_df, test_df, query_s = preprocess(ctx, session, table, parallelism,
                                            seed)
    ctx.say_time("preprocessing query (last_query_stats)", query_s)
    del table
    t = time.perf_counter()
    part_b = check_data(ctx, raw, train_df, test_df)
    ctx.say_time("part (b)", time.perf_counter() - t)
    del raw
    train = train_df.limit(train_rows)
    held = test_df.limit(int(tr["held_out_rows"]))
    module = make_model(ctx)
    t = time.perf_counter()
    part_a = check_arithmetic(ctx, module, held_out_arrays(ctx, held), seed)
    ctx.say_time("part (a) and the initial held-out loss", time.perf_counter() - t)
    ctx.say(f"device memory peak after part (a): {ctx.memory_peak_bytes()} bytes")
    part_c = warm_up(ctx, module, train, held, seed,
                     part_a["initial_held_out_loss"])
    if not ctx.rehearsal and part_c["peak_source"] != "tpu-table":
        raise RuntimeError(f"estimator's peak_source is "
                           f"{part_c['peak_source']!r}, not 'tpu-table'")

    ctx.say(f"device memory peak after the warm-up fit: "
            f"{ctx.memory_peak_bytes()} bytes")

    est = make_estimator(ctx, module, seed, num_epochs=1_000_000)
    failure = []

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
    hist_open = None
    trace_dir = ctx.path("trace")
    trace_state = "wait" if ctx.trace else "off"
    trace_until = 0.0
    annotation = None
    summary = None
    while True:
        done = len(est.history)
        now = time.perf_counter()
        if done > len(fences):
            fences.append((now, done))
            if t_open is None:
                t_open, wall_open = now, time.time()
                hist_open = layers.histogram_totals(STEP_HISTOGRAMS)
                ctx.say_time("the window's fit, start to its first fence "
                             "(the window opens)", now - t_fit)
            elif trace_state == "wait" and len(fences) >= 2:
                jax.profiler.start_trace(trace_dir)
                annotation = jax.profiler.TraceAnnotation(xplane.WINDOW_EVENT)
                annotation.__enter__()
                trace_state, trace_until = "on", now + float(tr["trace_seconds"])
        if trace_state == "on" and now >= trace_until:
            annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
            trace_state = "done"
        if failure:
            raise RuntimeError(f"the window's fit failed: {failure[0]!r}")
        if (t_open is not None and now >= t_open + ctx.seconds
                and trace_state in ("off", "done")):
            break
        time.sleep(0.002)
    hist_close = layers.histogram_totals(STEP_HISTOGRAMS)
    setup_s = wall_open - ctx.t0
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
            f"held-out loss {losses[-1]:.5f}")

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
        ctx.say(f"window: {rate['work']:.0f} samples between {rate['fences']} "
                f"fences over {rate['elapsed_s']:.3f} s = {rate['rate']:.1f} "
                f"samples/s; set-up {setup_s:.2f} s; on {dev['kind']} "
                f"x{dev['count']}")
    if ctx.trace and not ctx.rehearsal:
        peaks = peaks_for(dev["kind"])
        features = len(m["vocab_sizes"]) + 1
        sources = {
            "histograms": layers.histogram_deltas(hist_open, hist_close),
            "values": {
                "etl_query_s": query_s,
                "model_flops_utilization_pct": 100.0 * costs.dlrm_step_flops(
                    batch, m["num_dense"], m["embed_dim"], m["bottom_mlp"],
                    m["top_mlp"], len(m["vocab_sizes"]))
                * rate["rate"] / batch / peaks["flops_per_s"],
            },
            "trace": summary, "peaks": peaks,
            "kernels": {"dot_interaction": {"cost": costs.dot_interaction(
                batch, features, m["embed_dim"], 4)}},
        }
        result["metrics"] = layers.read_all(ctx.cell, sources)
        result["device"].update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = summary.breakdown()
    elif not ctx.rehearsal:
        result["metrics"] = metric_dict(ctx.cell, end_to_end)
    ctx.write(ctx.phase, result)
    # the window is closed: the process leaves. Executors and head first, so
    # that nothing of the run outlives it; the fit thread dies with us.
    try:
        raydp_tpu.stop_etl()
        cluster.shutdown()
    finally:
        os._exit(0)
