"""Row-wise optimizer update: a train step that differentiates and updates
only the rows of a parameter that the batch read.

A dense step differentiates with respect to a whole embedding table: XLA
zero-fills a ``[V, D]`` gradient, scatter-adds the batch's ``B`` rows into it
and runs the optimizer over all ``V`` rows of table and state, though at most
``B`` of them changed. Here the step gathers the ``<= B`` distinct rows the
batch reads, differentiates with respect to those, runs the SAME
``tx.update`` on the mini-tree (those rows of the parameter and of every
parameter-shaped leaf of the optimizer state, every other leaf whole) and
scatters the rows back in place. The pytrees of ``params`` and ``opt_state``
keep their structure and shapes. The rows go back through a kernel that owns
its DMAs (``ops/row_write_back.py``) where the leaf allows it, and through
XLA's scatter where not (:func:`_scatter_reason`); of those leaves, the ones
XLA's gather would copy whole to read a batch's rows are READ by the
kernel's mirror (``ops/row_gather.py``, :func:`_gather_reason`).

That is the same mathematics only where the optimizer is row-local and leaves
a row with a zero gradient, and its state, as they were. Nothing here knows
an optimizer by name: :func:`plan` looks at the state the transformation
keeps (nothing beside copies of the parameters, so that it cannot count
steps) and observes it on a toy tree (:func:`probe`). Three things decide,
all observed, none set by a user: the model (it has to declare
``row_gathers``), the optimizer (its state and the probe) and the shape (a
table of few rows is cheaper updated whole).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import numpy as np

from raydp_tpu import obs

# A declared table takes the row path from this many rows per row of the
# batch on. Measured on the v5e at batch 2048, embed 16, Adagrad, with the
# scatter of _put below (PERF.md, Findings, PR 25, chip call E): the whole
# step with this constant at 4 / 32 / 64 / 128 takes 5.88 / 5.80 / 5.78-5.80
# / 6.31 ms. At 6-7 rows per row of the batch (12,517 and 14,992 rows) the
# dense update is the cheaper by 0.04 ms a table, at 45 (93,145 rows) the
# two tie within 0.3 % of the step, at 70 (142,572 rows) the row path wins
# by 0.53 ms. The configuration measured has no table between 7 and 45:
# every value from 8 to 45 gives it the same program, and 32 is not
# resolved against its neighbours by any measurement. With the write-back
# kernel of PR 28 (which has no cost in the table's size) the whole step at
# 4 / 32 / 64 takes 4.38-4.43 / 4.20-4.30 / 4.42 ms (PERF.md, Findings, PR
# 28): the two tables of 12,517 and 14,992 rows are still cheaper dense.
MIN_ROWS_PER_BATCH_ROW = 32

# A leaf the write-back kernel takes is READ by the gather kernel too
# (ops/row_gather.py) up to this many rows: where XLA:TPU's gather first
# copies the whole table, every step. Compiled for a described v5e over a
# sweep of rows (2048 ids): up to 299,000 rows a relayout copy to the
# row-major layout (eight times the table's bytes: into VMEM where they fit,
# into HBM at 286,181 rows and over), from 300,000 to 1,800,000 rows a copy
# of the table into VMEM, from 2,000,000 on neither. A parameter with its
# state at the DLRM cells' ids through XLA's gather, us (chip runs, PERF.md
# Findings, PR 46): 16,384 rows 18; 65,536: 59; 93,145: 83; 142,572: 110;
# 286,181: 558; 299,000: 581; 320,000: 70; 1,000,000: 127; 1,800,000: 204;
# 2,000,000: 98; the five tables of 2.2-10.1 M rows 97-145. Through the
# kernel, whose time goes by the distinct ids and not by the table: 66 / 71 /
# 80 at 93,145 / 286,181 / 10.1 M rows. So the kernel is the faster from some
# 80,000 rows on, everywhere; but each table SHAPE it takes costs a process
# 0.1 s of tracing and 0.15 s a program of lowering, and set-up is paid for
# too: all eight shapes of the cells on the kernel gave 4 % of the step
# (3.76 ms for 3.92) for 4.7 s of a 53-s set-up, the three that XLA copies
# cost 1.8 s. The constant keeps the kernel to the tables XLA copies
GATHER_KERNEL_MAX_ROWS = 2_000_000

# marks, in an index tree, a leaf that is updated whole
_WHOLE = object()


@dataclass(frozen=True, eq=False)
class _Rows:
    """In an index tree, a leaf that is updated by rows: their ids (what
    :func:`sorted_unique` gives), whether the kernel writes them back and
    whether a kernel reads them. A parameter and the state that follows it
    hold the same object."""

    idx: Any
    kernel: bool = False
    gather: bool = False


@dataclass(frozen=True)
class RowPlan:
    """What one fit's step does: ``paths`` take the row path (empty: the
    dense step, for ``reason``); ``bytes_skipped`` is what the update no
    longer reads each step (the rows of those parameters and of their
    parameter-shaped optimizer state that the batch cannot have touched)."""

    paths: Tuple[Tuple[str, ...], ...] = ()
    bytes_skipped: int = 0
    reason: str = ""
    # the paths whose rows (the parameter's and its state's) the kernel
    # writes back, and how many leaves that makes; the other ``scatter_leaves``
    # go through XLA's scatter, for ``scatter_reason`` (the first found)
    kernel_paths: Tuple[Tuple[str, ...], ...] = ()
    kernel_leaves: int = 0
    scatter_leaves: int = 0
    scatter_reason: str = ""
    # the same for the rows' way in: ``gather_paths`` are read by the gather
    # kernel (of the ``kernel_paths``, those XLA's gather would copy whole:
    # :func:`_gather_reason`), the other ``xla_gather_leaves`` by XLA's gather
    gather_paths: Tuple[Tuple[str, ...], ...] = ()
    gather_leaves: int = 0
    xla_gather_leaves: int = 0
    gather_reason: str = ""

    def stats(self) -> Dict[str, Any]:
        return {
            "params": len(self.paths),
            "bytes_skipped": self.bytes_skipped,
            "reason": self.reason,
            "paths": ["/".join(p) for p in self.paths],
            "write_back": {
                "kernel": self.kernel_leaves,
                "scatter": self.scatter_leaves,
                "reason": self.scatter_reason,
            },
            "gather": {
                "kernel": self.gather_leaves,
                "xla": self.xla_gather_leaves,
                "reason": self.gather_reason,
            },
        }


def _path(key_path) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", k)) for k in key_path)


def _by_path(tree) -> Dict[Tuple[str, ...], Any]:
    import jax

    return {_path(kp): leaf for kp, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _index_tree(params, index: Dict[Tuple[str, ...], Any]):
    """``params``' structure with ``index[path]`` at the row-path leaves and
    the whole-leaf mark everywhere else."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda kp, _: index.get(_path(kp), _WHOLE), params
    )


def _state_index_tree(tx, opt_state, index_tree):
    """The index tree of ``opt_state``: every copy of the parameter tree
    inside it (optax finds them) carries ``index_tree``, the rest is whole."""
    import optax

    return optax.tree_map_params(
        tx, lambda _, i: i, opt_state, index_tree,
        transform_non_params=lambda _: _WHOLE,
    )


def _stateful_leaves(tx, opt_state) -> int:
    """How many leaves of ``opt_state`` lie outside the copies of the
    parameter tree in it: a step count, a schedule's, accumulated updates
    kept beside it, injected hyperparameters."""
    import jax
    import optax

    outside = optax.tree_map_params(
        tx, lambda _: False, opt_state, transform_non_params=lambda _: True
    )
    return sum(jax.tree.leaves(outside))


def _take(leaf, i, read=None):
    # the padding slots of idx lie past the last row: they read it (clip)
    # and are dropped again by _put. ``read``: the rows, where _read took them
    if read is not None:
        return read
    return leaf if i is _WHOLE else leaf.at[i.idx].get(mode="clip")


def _read(trees, indexes):
    """The rows of the leaves of ``trees`` that the gather kernel reads
    (``_Rows.gather``), in ``trees``' structure, None at every other leaf
    (:func:`_take` reads those). A parameter and its state share their ids,
    so the kernel reads them in one call, as :func:`_put` writes them."""
    import jax

    from raydp_tpu.ops.row_gather import row_gather

    leaves, treedef = jax.tree.flatten(trees)
    index = treedef.flatten_up_to(indexes)
    rows = [None] * len(leaves)
    for where in _kernel_calls(index, "gather"):
        for n, got in zip(where, row_gather(
                [leaves[n] for n in where], index[where[0]].idx)):
            rows[n] = got
    return treedef.unflatten(rows)


def _kernel_calls(index, which="kernel"):
    """The leaves a kernel takes (``which``: the write-back ``kernel`` or the
    ``gather`` kernel; their positions in ``index``, a flat list of an index
    tree's leaves), a list for each set of ids: a parameter and the state
    that follows it go into one call."""
    calls: Dict[int, list] = {}
    for n, i in enumerate(index):
        if i is not _WHOLE and getattr(i, which):
            calls.setdefault(id(i), []).append(n)
    return list(calls.values())


def _scatter_reason(leaf) -> str:
    """Why the rows of ``leaf`` (an array, or its shape and dtype) go back
    through XLA's scatter and not through the kernel; empty where the kernel
    takes them. The kernel moves blocks of the table's own device layout by
    DMA: that layout is a TPU's, and a Pallas call is not partitioned."""
    import jax

    from raydp_tpu.ops import backend, row_write_back

    if not backend.on_tpu():
        return f"the backend is {jax.default_backend()}, not a TPU"
    sharding = getattr(leaf, "sharding", None)
    devices = len(sharding.device_set) if sharding is not None else 1
    if devices > 1:
        return (f"the leaf lies on {devices} devices, and a Pallas call is "
                "not partitioned")
    return row_write_back.supports(leaf.shape, leaf.dtype)


def _gather_reason(leaf) -> str:
    """Why the rows of ``leaf``, which the kernel writes back, are read by
    XLA's gather and not by the gather kernel; empty where the kernel reads
    them. The shape decides: the kernel goes where XLA's gather would copy
    the table whole."""
    if leaf.shape[0] > GATHER_KERNEL_MAX_ROWS:
        return (f"XLA's gather reads a table of {leaf.shape[0]} rows where "
                f"it lies (it copies one of {GATHER_KERNEL_MAX_ROWS} or less)")
    return ""


def _put(trees, minis, indexes):
    """``trees`` with the rows of ``minis`` written back where ``indexes``
    says. A parameter and its state share their ids, so the kernel writes
    them in one call: one pass over the ids, twice the transfers in flight."""
    import jax

    from raydp_tpu.ops.row_write_back import row_write_back

    leaves, treedef = jax.tree.flatten(trees)
    rows = treedef.flatten_up_to(minis)
    index = treedef.flatten_up_to(indexes)
    for n, (leaf, new, i) in enumerate(zip(leaves, rows, index)):
        if i is _WHOLE:
            leaves[n] = new
        elif not i.kernel:
            # idx is sorted and without repeats, and XLA is not told:
            # promised both, XLA:TPU scatters into a table of
            # 100,000-300,000 rows in time proportional to the table, 0.44
            # ms against 0.14 (PERF.md, PR 25)
            leaves[n] = leaf.at[i.idx].set(new, mode="drop")
    for where in _kernel_calls(index):
        for n, leaf in zip(where, row_write_back(
                [leaves[n] for n in where], [rows[n] for n in where],
                index[where[0]].idx)):
            leaves[n] = leaf
    return treedef.unflatten(leaves)


def sorted_unique(ids, sizes):
    """Distinct ids of each row of ``ids`` (int32 ``[S, N]``, row ``s`` in
    ``[0, sizes[s])``), by one batched sort and not one ``jnp.unique`` a row.
    Returns ``(uniq, inv)``, both int32 ``[S, N]``: ``uniq[s]`` ascending and
    without repeats, the distinct ids first and then padding from
    ``sizes[s]`` up (past the last row, so a scatter drops it);
    ``uniq[s, inv[s, n]] == ids[s, n]``."""
    import jax.numpy as jnp
    from jax import lax

    iota = lax.broadcasted_iota(jnp.int32, ids.shape, 1)
    sorted_ids, order = lax.sort_key_val(ids, iota, dimension=1)
    first = jnp.concatenate(
        [
            jnp.ones((ids.shape[0], 1), bool),
            sorted_ids[:, 1:] != sorted_ids[:, :-1],
        ],
        axis=1,
    )
    # the slot of each sorted id among its row's distinct ones
    slot = jnp.cumsum(first, axis=1, dtype=jnp.int32) - 1
    pad = jnp.asarray(sizes, jnp.int32)[:, None] + iota
    uniq = lax.sort(jnp.where(first, sorted_ids, pad), dimension=1)
    _, inv = lax.sort_key_val(order, slot, dimension=1)  # the batch's order
    return uniq, inv


def update_rows(tx, params, opt_state, mini_params, mini_grads, index_tree,
                state_read=None):
    """``tx.update`` on the mini-tree and the scatter back: the dense
    ``tx.update`` + ``apply_updates`` where ``tx`` passes :func:`probe`.
    ``state_read``: the state's rows that :func:`_read` took beside the
    parameters' (None: none)."""
    import jax
    import optax

    state_index = _state_index_tree(tx, opt_state, index_tree)
    if state_read is None:
        state_read = jax.tree.map(lambda _: None, opt_state)
    mini_state = jax.tree.map(_take, opt_state, state_index, state_read)
    updates, mini_state = tx.update(mini_grads, mini_state, mini_params)
    mini_params = optax.apply_updates(mini_params, updates)
    return _put(
        (params, opt_state), (mini_params, mini_state),
        (index_tree, state_index),
    )


def step(module, loss_fn, tx, paths, params, opt_state, x, y, kernel_paths=(),
         gather_paths=()):
    """One train step with ``paths`` on the row path, those of
    ``kernel_paths`` written back by the kernel and those of
    ``gather_paths`` read by one. Returns ``(params, opt_state, loss)`` as
    the dense step does."""
    import jax
    import jax.numpy as jnp

    with obs.device_scope("loss_and_grad"):
        whole = _by_path(params)
        ids = module.row_gathers(x)
        uniq, inv = sorted_unique(
            jnp.stack([ids[p] for p in paths]),
            [whole[p].shape[0] for p in paths],
        )
        index_tree = _index_tree(params, {
            p: _Rows(uniq[s], p in kernel_paths, p in gather_paths)
            for s, p in enumerate(paths)})
        # the gather kernel reads a parameter's rows and its state's in one
        # call: here, for both (what XLA's gather reads of the state it
        # reads in update_rows, as ever)
        params_read, state_read = _read(
            (params, opt_state),
            (index_tree, _state_index_tree(tx, opt_state, index_tree)))
        mini_params = jax.tree.map(_take, params, index_tree, params_read)

        def compute(mini):
            rows = _by_path(mini)
            # the model gets every table whole, as a constant of the
            # derivative, and reads none of those it is handed rows of: a
            # sample's row is its distinct row's, so the derivative sums the
            # gradients of repeated ids (Adagrad needs (sum g)^2, not
            # sum g^2)
            variables = jax.tree.map(
                lambda full, m, i: m if i is _WHOLE else full,
                params, mini, index_tree,
            )
            gathered = {p: rows[p][inv[s]] for s, p in enumerate(paths)}
            return loss_fn(module.apply(variables, x, rows=gathered), y)

        loss, mini_grads = jax.value_and_grad(compute)(mini_params)
    with obs.device_scope("optimizer_update"):
        params, opt_state = update_rows(
            tx, params, opt_state, mini_params, mini_grads, index_tree,
            state_read,
        )
    return params, opt_state, loss


def probe(tx, params, paths) -> Optional[str]:
    """Observe whether ``tx`` may take the row path: None, or the reason it
    may not. On a toy tree of ``params``' structure (8 rows of 4 to a
    row-path leaf, 4 to every axis elsewhere), off the accelerator, bit for
    bit:

    - two dense steps whose gradients touch two rows each, not the same two,
      against :func:`update_rows` on the same gradients: parameters and
      every leaf of the state. A moment that decays at a zero gradient
      (Adam), weight decay, or statistics shared between rows (Adafactor's
      factors, a trust ratio) show here;
    - the first dense step again with every OTHER gradient changed: the
      touched rows' updates and state must not move. Anything that couples
      a row to the rest of the tree (clipping by the global norm) shows
      here.

    Operation by operation and not under ``jit``: fused, the two sides of a
    comparison round differently (they are fused differently) and nothing
    passes. Few shapes, so that few operations compile: about a second in a
    process's first fit, a fifth of one after."""
    import jax
    import jax.numpy as jnp
    import optax

    rows, touched = 8, ((1, 5), (2, 7))
    rng = np.random.default_rng(0)

    def toy(kp, leaf):
        shape = (rows, 4) if _path(kp) in paths else (4,) * leaf.ndim
        return jnp.asarray(rng.standard_normal(shape), leaf.dtype)

    def on_rows(hit, touched_rows, elsewhere):
        """A row-path leaf's ``touched_rows`` on the rows ``hit``,
        ``elsewhere`` on its other rows."""
        mask = np.zeros((rows, 1), bool)
        mask[list(hit)] = True
        return jnp.where(mask, touched_rows, elsewhere)

    def observe():
        p0 = jax.tree_util.tree_map_with_path(toy, params)
        s0 = tx.init(p0)
        dense = rowwise = (p0, s0)
        for step_no, hit in enumerate(touched):
            index_tree = _index_tree(
                p0, {p: _Rows(jnp.asarray(hit + (rows, rows + 1), jnp.int32))
                     for p in paths})
            grads = jax.tree.map(
                lambda g, i: g if i is _WHOLE else on_rows(hit, g, 0.0),
                jax.tree_util.tree_map_with_path(toy, params), index_tree)
            if step_no == 0:
                # the same step, every gradient but the touched rows' changed
                other = jax.tree.map(
                    lambda g, i: 2 * g + 1 if i is _WHOLE
                    else on_rows(hit, g, 1.0), grads, index_tree)
                near = jnp.asarray(hit, jnp.int32)
                alone, coupled = (
                    jax.tree.map(
                        lambda leaf, i: () if i is _WHOLE else leaf[near],
                        tx.update(g, s0, p0),
                        (index_tree, _state_index_tree(tx, s0, index_tree)))
                    for g in (grads, other))
            p, s = dense
            updates, s = tx.update(grads, s, p)
            dense = (optax.apply_updates(p, updates), s)
            p, s = rowwise
            rowwise = update_rows(
                tx, p, s, jax.tree.map(_take, p, index_tree),
                jax.tree.map(
                    lambda g, i: g if i is _WHOLE else g.at[i.idx].get(
                        mode="fill", fill_value=0), grads, index_tree),
                index_tree)
        return dense, rowwise, alone, coupled

    def same(a, b):
        la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
        return len(la) == len(lb) and all(
            np.array_equal(np.asarray(u), np.asarray(v), equal_nan=True)
            for u, v in zip(la, lb))

    try:
        try:
            device = jax.devices("cpu")[0]
        except RuntimeError:  # no CPU backend in this process: where we are
            device = None
        with jax.default_device(device):
            dense, rowwise, alone, coupled = observe()
    except Exception as exc:  # noqa: BLE001 - any optimizer a user passes; the dense step needs none of this
        return f"the optimizer could not be observed on rows ({exc!r:.200})"
    if not same(dense, rowwise):
        return ("the optimizer is not row-wise: on a toy tree, two steps on "
                "the rows a gradient touched differ from two dense steps")
    if not same(alone, coupled):
        return ("the optimizer couples rows: on a toy tree, a row's update "
                "moved when only other gradients changed")
    return None


def plan(module, tx, params, x, batch: int) -> RowPlan:
    """Decide one fit's step from what can be observed of it."""
    import jax

    declare = getattr(module, "row_gathers", None)
    if declare is None:
        return RowPlan(reason="the model declares no row-gathered parameters")
    whole = _by_path(params)
    declared = tuple(jax.eval_shape(declare, x))
    paths = tuple(
        p for p in declared
        if whole[p].shape[0] >= MIN_ROWS_PER_BATCH_ROW * batch
    )
    if not paths:
        return RowPlan(reason=(
            f"none of the {len(declared)} declared parameters has "
            f"{MIN_ROWS_PER_BATCH_ROW} rows to a row of the batch ({batch})"
        ))
    # a row-path leaf carries its parameter's path, here only
    index_tree = _index_tree(params, {p: "/".join(p) for p in paths})
    state = jax.eval_shape(tx.init, params)
    try:
        state_index = _state_index_tree(tx, state, index_tree)
    except Exception as exc:  # noqa: BLE001 - optax's own assertions, on any optimizer a user passes
        return RowPlan(reason=(
            "optax cannot tell which leaves of this optimizer's state follow "
            f"the parameters (tree_map_params: {exc!r:.160})"
        ))
    # The probe watches two steps. What an update does on a later step it
    # can only have learned from the state, and a parameter-shaped leaf the
    # probe has seen through (a row's state moves with that row's gradient
    # alone). A leaf outside the parameter copies is where a transformation
    # counts steps: apply_every(k) pays out every k-th step to all rows, a
    # schedule switches a weight decay on at step n. Without one, the two
    # steps are enough.
    outside = _stateful_leaves(tx, state)
    if outside:
        return RowPlan(reason=(
            f"the optimizer keeps state beside the parameters' ({outside} "
            "leaf or leaves: a step count, a schedule's): what it does on a "
            "later step cannot be observed in two"
        ))
    why = probe(tx, params, paths)
    if why:
        return RowPlan(reason=why)
    skipped = 0
    leaves = {"/".join(p): 0 for p in paths}
    scatter: Dict[str, str] = {}  # the paths with a leaf the kernel refuses
    xla: Dict[str, str] = {}  # the paths whose rows XLA's gather reads
    for tree, index in ((params, index_tree), (state, state_index)):
        for leaf, i in zip(jax.tree.leaves(tree), jax.tree.leaves(index)):
            if i is not _WHOLE:
                row = math.prod(leaf.shape[1:]) * leaf.dtype.itemsize
                skipped += (leaf.shape[0] - batch) * row
                leaves[i] += 1
                why = _scatter_reason(leaf)
                if why:
                    scatter.setdefault(i, why)
                why = why or _gather_reason(leaf)
                if why:
                    xla.setdefault(i, why)
    kernel_paths = tuple(p for p in paths if "/".join(p) not in scatter)
    kernel_leaves = sum(leaves["/".join(p)] for p in kernel_paths)
    gather_paths = tuple(p for p in paths if "/".join(p) not in xla)
    gather_leaves = sum(leaves["/".join(p)] for p in gather_paths)
    return RowPlan(
        paths=paths, bytes_skipped=skipped, kernel_paths=kernel_paths,
        kernel_leaves=kernel_leaves,
        scatter_leaves=sum(leaves.values()) - kernel_leaves,
        scatter_reason=next(iter(scatter.values()), ""),
        gather_paths=gather_paths, gather_leaves=gather_leaves,
        xla_gather_leaves=sum(leaves.values()) - gather_leaves,
        gather_reason=next(iter(xla.values()), ""),
    )
