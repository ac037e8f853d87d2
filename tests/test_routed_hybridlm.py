"""Routed experts (ops/experts.py) and the ``lfm2_moe`` family of
models/hybridlm.py at tiny sizes, float32, seeded random weights: the expert
layer against a dense 0/1-mask computation, its shares against the uncut
layer, the edges of the load; the model against the plain reference
(benchmark/reference/lfm2_moe.py) in loss, logits and every gradient; the
mutations the comparison must catch; ``from_config``; ``fit_facts``; and a
JaxEstimator fit on an ETL frame that reports the experts' load of its
TRAINING steps, moves ``expert_bias`` by the balancing rule, and whose epoch
program is held to the reference's gradients through its optimizer (AdamW
under a warm-up, the rule on the biases)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.reference import lfm2_moe as ref  # noqa: E402
from raydp_tpu.models import (  # noqa: E402
    HybridLM, RoutedHybridLM, hybridlm_optimizer)
from raydp_tpu.ops import experts  # noqa: E402

V, T = 256, 32
LAYER_TYPES = ["conv", "conv", "full_attention", "conv", "conv", "conv",
               "full_attention", "conv"]
# the published keys at tiny widths: published layers 1-5, experts 2-3 of 8
CONFIG = {
    "model_type": "lfm2_moe", "vocab_size": V, "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "layer_types": LAYER_TYPES, "num_hidden_layers": 5, "num_dense_layers": 1,
    "num_experts": 2, "num_experts_per_tok": 2, "conv_L_cache": 3,
    "conv_bias": False, "norm_eps": 1e-5, "norm_topk_prob": True,
    "rope_theta": 1000000, "routed_scaling_factor": 1, "use_expert_bias": True,
    "share": {"first_layer": 1, "experts_total": 8, "first_expert": 2}}
CFG = ref.config_of(CONFIG)


def model(cls=RoutedHybridLM, config=CONFIG, **kw):
    return cls.from_config(config, **{
        "dtype": jnp.float32, "loss_chunk": 16, "expert_bias_spread": 0.05,
        **kw})


# -- the expert layer alone ----------------------------------------------------

N, D, F, E, K = 48, 16, 8, 8, 2


@pytest.fixture(scope="module")
def layer():
    keys = jax.random.split(jax.random.PRNGKey(3), 5)
    return {"u": jax.random.normal(keys[0], (N, D)),
            "w_gate": jax.random.normal(keys[1], (D, E)),
            "bias": 0.3 * jax.random.normal(keys[2], (E,)),
            "w13": 0.3 * jax.random.normal(keys[3], (E, D, 2 * F)),
            "w2": 0.3 * jax.random.normal(keys[4], (E, F, D))}


def masked(u, w_gate, bias, w13, w2, first, count):
    """Every held expert on every token under a 0/1 mask (the reference's
    way, written out again here)."""
    scores = jax.nn.sigmoid(u @ w_gate)
    _, sel = jax.lax.top_k(scores + bias, K)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    w = w / (w.sum(-1, keepdims=True) + 1e-6)
    out = jnp.zeros_like(u)
    for e in range(first, first + count):
        h = u @ w13[e]
        y = (jax.nn.silu(h[:, :F]) * h[:, F:]) @ w2[e]
        out = out + jnp.where(sel == e, w, 0).sum(-1)[:, None] * y
    return out, sel


def share_of(p, first, count, **kw):
    with jax.default_matmul_precision("highest"):
        return experts.routed_experts(
            p["u"], p["w_gate"], p["bias"], p["w13"][first:first + count],
            p["w2"][first:first + count], first=first,
            **{"top_k": K, **kw})


@pytest.mark.parametrize("first, count", [(0, 8), (2, 2), (6, 2)])
def test_the_layer_is_the_masked_sum_forward_and_backward(layer, first, count):
    def ours(u, w_gate, w13, w2):
        out, _ = share_of({**layer, "u": u, "w_gate": w_gate, "w13": w13,
                           "w2": w2}, first, count)
        return (out * jnp.cos(jnp.arange(D))).sum(), out

    def theirs(u, w_gate, w13, w2):
        with jax.default_matmul_precision("highest"):
            out, _ = masked(u, w_gate, layer["bias"], w13, w2, first, count)
        return (out * jnp.cos(jnp.arange(D))).sum(), out

    args = (layer["u"], layer["w_gate"], layer["w13"], layer["w2"])
    (_, got), g_got = jax.value_and_grad(ours, (0, 1, 2, 3), has_aux=True)(*args)
    (_, want), g_want = jax.value_and_grad(theirs, (0, 1, 2, 3), has_aux=True)(
        *args)
    assert float(jnp.abs(got - want).max()) <= 1e-5
    for a, b in zip(g_got, g_want):
        assert float(jnp.abs(a - b).max()) <= 1e-4 * max(
            1.0, float(jnp.abs(b).max()))
    # the weights of experts that are not held take no gradient at all
    outside = np.ones(E, bool)
    outside[first:first + count] = False
    assert not np.asarray(g_got[2])[outside].any()


def test_the_four_shares_add_up_to_the_uncut_layer(layer):
    """Experts 0-1, 2-3, 4-5, 6-7 on four chips: each routes over all 8 and
    computes its own experts' part; the parts add up to the layer with all
    8 held, and every share reports the same selection."""
    whole, report = share_of(layer, 0, 8)
    parts = [share_of(layer, first, 2) for first in (0, 2, 4, 6)]
    assert float(jnp.abs(sum(out for out, _ in parts) - whole).max()) <= 1e-5
    for _, said in parts:
        assert bool((said["sel"] == report["sel"]).all())
    loads = np.concatenate([np.asarray(said["load"]) for _, said in parts])
    assert loads.tolist() == np.asarray(report["load"]).tolist()
    assert loads.sum() == N * K
    assert all(float(said["dropped"]) == 0 for _, said in parts)


@pytest.mark.parametrize("favoured, load", [((2, 3), [N, N]), ((0, 1), [0, 0])])
def test_every_token_here_and_no_token_here(layer, favoured, load):
    """A bias that sends every token to the two held experts, and one that
    sends none: nothing dropped, the result right, the gradients finite."""
    bias = jnp.zeros((E,)).at[jnp.asarray(favoured)].set(10.0)
    p = {**layer, "bias": bias}
    out, report = share_of(p, 2, 2)
    want, _ = masked(p["u"], p["w_gate"], bias, p["w13"], p["w2"], 2, 2)
    assert np.asarray(report["load"]).tolist() == load
    assert float(report["dropped"]) == 0
    assert float(jnp.abs(out - want).max()) <= 1e-5
    assert bool(out.any()) == bool(load[0])
    grads = jax.grad(lambda u, w13: share_of(
        {**p, "u": u, "w13": w13}, 2, 2)[0].sum(), (0, 1))(p["u"], p["w13"])
    assert all(bool(jnp.isfinite(g).all()) for g in grads)


def test_a_row_bound_too_small_is_counted_not_hidden(layer):
    """Pairs past the rows' buffer are left out AND counted; the default
    bound (tokens x k) never drops."""
    whole, report = share_of(layer, 0, 8)
    assert float(report["dropped"]) == 0
    cut, said = share_of(layer, 0, 8, row_bound=N * K - 5)
    assert float(said["dropped"]) == 5
    assert float(jnp.abs(cut - whole).max()) > 1e-3
    assert experts.row_bound_for(32768 * 4) == 131072
    assert experts.row_bound_for(100) == experts.ROW_TILE


# -- the plan: the sort, and the held pairs in token order -----------------------

# experts 2-3 held, top-3: token 0 holds none of its choices, token 1 all
# three (a repeated choice is no router's, and the sort does not care), the
# others one or two; 8 held pairs
CHOICES = np.array([[0, 1, 5], [2, 3, 2], [7, 2, 0], [3, 6, 2], [1, 0, 4],
                    [3, 7, 6], [2, 5, 6]], np.int32)


@pytest.mark.parametrize("row_bound", [
    8,    # the load fills the bound exactly
    11,   # rows past the filled ones
    6,    # two held pairs past the bound: left out of every list
    experts.ROW_TILE])
def test_the_plan_lists_the_held_pairs_in_token_order(row_bound):
    """``by_token`` / ``pair_by_token`` / ``head`` against a loop over the
    pairs in their flat order: slot by slot the row and the pair of the
    pairs that are valid, the sentinel past them, and every token's first
    slot, held or not."""
    n, k = CHOICES.shape
    p = jax.tree.map(np.asarray, experts.plan(
        jnp.asarray(CHOICES), 2, 2, row_bound))
    held = (CHOICES >= 2) & (CHOICES < 4)
    # the rows: by expert, inside an expert by flat index
    flat = sorted(np.flatnonzero(held.ravel()),
                  key=lambda f: (CHOICES.ravel()[f], f))
    row_of = {f: r for r, f in enumerate(flat) if r < row_bound}
    assert int(p["rows"]) == len(row_of) == min(8, row_bound)
    assert int(p["dropped"]) == 8 - len(row_of)
    rows, pairs, head = [], [], []
    for f in range(n * k):
        if f % k == 0:
            head.append(len(rows))
        if f in row_of:
            rows.append(row_of[f])
            pairs.append(f)
    filled = len(rows)
    assert p["by_token"].shape == p["pair_by_token"].shape == (row_bound,)
    assert p["by_token"][:filled].tolist() == rows
    assert p["pair_by_token"][:filled].tolist() == pairs
    assert (p["pair_by_token"][filled:] == n * k).all()
    assert (p["by_token"] < row_bound).all() and (p["by_token"] >= 0).all()
    assert p["head"].tolist() == head
    # the same pairs as the rows' side lists, the other way round
    assert (p["pair"][p["by_token"][:filled]] == pairs).all()
    assert (p["tok"][p["by_token"][:filled]] == np.array(pairs) // k).all()
    assert p["valid"].sum(1).tolist() == np.diff(head + [filled]).tolist()
    if row_bound >= 8:
        assert p["valid"].sum(1).tolist() == [0, 3, 1, 2, 0, 1, 1]


@pytest.mark.parametrize("weighted", [False, True])
@pytest.mark.parametrize("dtype, width", [
    (jnp.float32, D), (jnp.bfloat16, D), (jnp.bfloat16, 2 * experts.LANES)])
def test_a_tokens_sum_is_the_same_sum_in_either_form(dtype, width, weighted):
    """``_sum_per_token`` token-ordered (a buffer of 8 rows for 7 tokens of
    3 choices) against per choice (the same plan at 24 rows), operation by
    operation (a compiler may contract a product into the sum after it in
    one form and not in the other): the same bits, in float32 and in
    bfloat16, and the sum written out in numpy. At a width of whole tiles
    the token-ordered form works on rows of one tile each."""
    n, k = CHOICES.shape
    p = experts.plan(jnp.asarray(CHOICES), 2, 2, n * k + 3)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    rows = jax.random.normal(keys[0], (n * k + 3, width)).astype(dtype)
    w = jax.random.uniform(keys[1], (n, k)) if weighted else None
    assert experts.token_ordered(8, n, k)
    assert not experts.token_ordered(n * k + 3, n, k)
    assert experts.token_rows_gathered(8, n, k) == 8 + n
    assert experts.token_rows_gathered(n * k + 3, n, k) == n * k
    with jax.disable_jit():
        ordered = experts._sum_per_token(rows[:8], experts._plan_at(p, 8), w)
        per_choice = experts._sum_per_token(
            rows, experts._plan_at(p, n * k + 3), w)
    assert ordered.dtype == per_choice.dtype == dtype
    assert np.array_equal(np.asarray(ordered, np.float32),
                          np.asarray(per_choice, np.float32))
    want = np.zeros((n, width), np.float32)
    rank, valid = np.asarray(p["rank"]), np.asarray(p["valid"])
    for token in range(n):
        for j in range(k):
            if valid[token, j]:
                part = np.asarray(rows[rank[token, j]], np.float32)
                want[token] += part * np.float32(w[token, j]) if weighted else part
    assert np.array_equal(np.asarray(ordered, np.float32),
                          np.asarray(jnp.asarray(want).astype(dtype), np.float32))
    assert not np.asarray(ordered[0]).any() and np.asarray(ordered[1]).any()


# -- the rows' bound follows the load ------------------------------------------

# enough pairs for a likely bound under the worst case: 1024 pairs, experts
# 2-3 of 8 held: 512 rows (SLACK x 256, a whole tile) for 1024
MANY = 512


def _crowd(layer, tokens):
    """``layer`` with ``tokens`` tokens, and the same with a bias that sends
    every token's first two choices to the held experts 2-3."""
    u = jax.random.normal(jax.random.PRNGKey(5), (tokens, D))
    crowded = jnp.zeros((E,)).at[jnp.asarray((2, 3))].set(10.0)
    return {"even": {**layer, "u": u},
            "overflow": {**layer, "u": u, "bias": crowded}}


@pytest.fixture(scope="module")
def wide(layer):
    """``MANY`` tokens: 1024 rows filled where the load overflows."""
    return _crowd(layer, MANY)


def _out_and_grads(p, wrap=lambda f: f, dtype=jnp.float32, **kw):
    """(out, report, the five gradients: u, w_gate, bias, w13, w2) of the
    share 2-3, the tokens in ``dtype``; ``wrap`` is applied to the layer (a
    ``jax.checkpoint``)."""
    def run(u, w_gate, bias, w13, w2):
        out, report = wrap(lambda *a: share_of(
            dict(zip(("u", "w_gate", "bias", "w13", "w2"), a)), 2, 2, **kw))(
                u, w_gate, bias, w13, w2)
        return (out.astype(jnp.float32) * jnp.cos(jnp.arange(D))).sum(), (
            out, report)

    (_, (out, report)), grads = jax.jit(jax.value_and_grad(
        run, (0, 1, 2, 3, 4), has_aux=True))(
            p["u"].astype(dtype), p["w_gate"], p["bias"], p["w13"], p["w2"])
    return out, report, grads


def _same(got, want):
    return all(np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


def _eqns(jaxpr, name):
    """Every equation of primitive ``name`` in ``jaxpr`` and below it."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == name:
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub, name)


def test_the_bounds_of_a_share_and_of_the_uncut_layer():
    pairs = MANY * K
    assert experts.likely_row_bound(pairs, 2, 8) == 512 < experts.row_bound_for(
        pairs)
    assert experts.likely_row_bound(pairs, 8, 8) == experts.row_bound_for(pairs)
    # the cell's: 32,768 tokens, top-4, 8 of 32 held
    assert experts.likely_row_bound(131072, 8, 32) == experts.row_bound_for(
        int(experts.SLACK * 32768))
    assert 1.25 <= experts.SLACK <= 2.0


def test_the_form_of_the_token_side_follows_the_rows_gathered():
    """Token-ordered where that gathers fewer rows than a gather a choice:
    the cell's likely arm (40,960 + 32,768 rows for 131,072); per choice at
    the worst case and in a layer that holds every expert, where the buffer
    is tokens x k rows and the other form would gather 5 N for 4 N."""
    n, k = 32768, 4
    likely = experts.likely_row_bound(n * k, 8, 32)
    assert experts.token_ordered(likely, n, k)
    assert experts.token_rows_gathered(likely, n, k) == 40960 + 32768
    for bound in (experts.row_bound_for(n * k),
                  experts.likely_row_bound(n * k, 32, 32)):
        assert not experts.token_ordered(bound, n, k)
        assert experts.token_rows_gathered(bound, n, k) == n * k
    # as many rows either way: per choice, the form that was there
    assert not experts.token_ordered(512, 512, 2)


# (load, tokens, top_k, dtype): at 512 tokens of 2 choices both bounds run per
# choice (512 + 512 rows are no fewer than 2 x 512); at 768 of 3 the likely
# bound (1024 rows for 2304 pairs) runs token-ordered and the worst case per
# choice, so the equality below is the new form against the old
BY_LOAD = [("even", MANY, 2, jnp.float32), ("overflow", MANY, 2, jnp.float32),
           ("even", 768, 3, jnp.float32), ("overflow", 768, 3, jnp.float32),
           ("even", 768, 3, jnp.bfloat16)]


@pytest.mark.parametrize("load, tokens, top_k, dtype", BY_LOAD)
def test_the_layer_at_its_own_bound_is_the_layer_at_the_worst_case(
        layer, load, tokens, top_k, dtype):
    """With a share held the layer runs at the likely bound where the load
    fits it and at the worst-case one where it does not (``full_bound`` 1,
    nothing dropped): either way the output and all five gradients are the
    explicit worst-case layer's, exactly. WHICH FORM the token side ran in is
    read off the gathers each side makes: the likely arm's token-ordered
    where that gathers fewer rows, the explicit worst case's per choice."""
    p = _crowd(layer, tokens)[load]
    worst = experts.row_bound_for(tokens * top_k)
    likely = experts.likely_row_bound(tokens * top_k, 2, 8)
    assert experts.token_ordered(likely, tokens, top_k) == (top_k == 3)
    assert not experts.token_ordered(worst, tokens, top_k)

    def row_gathers(**kw):
        jaxpr = jax.make_jaxpr(lambda u: share_of(
            {**p, "u": u}, 2, 2, top_k=top_k, **kw)[0])(p["u"]).jaxpr
        shapes = [eqn.outvars[0].aval.shape for eqn in _eqns(jaxpr, "gather")]
        return sorted(shape[0] for shape in shapes if shape[1:] == (D,))

    def gathers_at(bound):  # the dispatch's, then the combine's
        # (token-ordered: k - 1 slots past the last for the shifts' sake)
        return [bound] + ([bound + top_k - 1, tokens] if experts.token_ordered(
            bound, tokens, top_k) else [tokens] * top_k)

    assert row_gathers(row_bound=worst) == sorted(gathers_at(worst))
    assert row_gathers() == sorted(gathers_at(likely) + gathers_at(worst))

    out, report, grads = _out_and_grads(p, dtype=dtype, top_k=top_k)
    want = _out_and_grads(p, dtype=dtype, top_k=top_k, row_bound=worst)
    rows = float(report["load"].sum())
    assert float(report["dropped"]) == 0 and float(want[1]["full_bound"]) == 0
    if load == "overflow":
        assert rows == tokens * 2 and float(report["full_bound"]) == 1
    else:
        assert 0 < rows <= likely and float(report["full_bound"]) == 0
    assert bool(out.any()) and all(bool(g.any()) for g in grads)
    if dtype == jnp.bfloat16:
        # the rows' dtype rounds the sums alike; the experts' weights'
        # gradients leave XLA:CPU's expanded grouped product in bfloat16
        # rounded by the rows a program holds, not by the form
        assert _same((out, grads[:3]), (want[0], want[2][:3]))
        for a, b in zip(grads[3:], want[2][3:]):
            assert float(jnp.abs(a - b).max()) <= 1e-2 * float(jnp.abs(b).max())
    elif load == "even" and top_k == 3:
        # float32 under jit: XLA:CPU contracts a product into the sum after
        # it (one rounding for two) where a form's fusion lets it, so the
        # forms agree to that rounding; operation by operation they agree
        # exactly (test_a_tokens_sum_is_the_same_sum_in_either_form)
        assert float(jnp.abs(out - want[0]).max()) <= 1e-6 * float(
            jnp.abs(want[0]).max())
        assert _same(grads, want[2])
    else:
        assert _same((out, grads), (want[0], want[2]))


@pytest.mark.parametrize("load, tokens, top_k", [
    ("even", MANY, 2), ("overflow", MANY, 2), ("even", 768, 3)])
def test_a_recomputed_layer_under_the_models_policy_has_the_same_gradients(
        layer, load, tokens, top_k):
    """Inside ``jax.checkpoint`` with the names a ``HybridLM`` block keeps,
    both branches, and both forms of the token side, give the gradients of
    the layer without recomputation."""
    from raydp_tpu.models import hybridlm

    keeps = hybridlm.REMAT_KEEPS + hybridlm.EXPERT_KEEPS
    policy = jax.checkpoint_policies.save_only_these_names(*keeps)
    p = _crowd(layer, tokens)[load]
    plain = _out_and_grads(p, top_k=top_k)
    again = _out_and_grads(
        p, wrap=lambda f: jax.checkpoint(f, policy=policy), top_k=top_k)
    assert float(again[1]["full_bound"]) == (load == "overflow")
    assert float(jnp.abs(again[0] - plain[0]).max()) <= 1e-6
    for a, b in zip(again[2], plain[2]):
        assert float(jnp.abs(a - b).max()) <= 1e-5 * max(
            1.0, float(jnp.abs(b).max()))


def test_a_recomputed_layer_neither_sorts_nor_chooses_again(layer):
    """What ``KEPT`` names is all of the layer's discrete part: the
    recomputed block (the ``remat2`` equation of the backward pass) holds
    no sort, no top-k and no cumulative sum, with the token order's lists
    among the names as with the sort's."""
    from raydp_tpu.models import hybridlm

    p = _crowd(layer, 768)["even"]
    policy = jax.checkpoint_policies.save_only_these_names(
        *hybridlm.REMAT_KEEPS, *hybridlm.EXPERT_KEEPS)

    def loss(u, w_gate, w13, w2, policy):
        return jax.checkpoint(lambda *a: share_of(
            {**p, **dict(zip(("u", "w_gate", "w13", "w2"), a))}, 2, 2,
            top_k=3)[0].sum(), policy=policy)(u, w_gate, w13, w2)

    def discrete(policy, inside):
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda *a: loss(*a, policy), (0, 1, 2, 3)))(
                p["u"], p["w_gate"], p["w13"], p["w2"]).jaxpr
        blocks = list(_eqns(jaxpr, "remat2"))
        assert len(blocks) == 1
        where = blocks[0].params["jaxpr"] if inside else jaxpr
        return {name: len(list(_eqns(where, name)))
                for name in ("sort", "top_k", "cumsum")}

    # the one cumulative sum left is the held experts' loads' (two numbers)
    assert discrete(policy, inside=True) == {"sort": 0, "top_k": 0, "cumsum": 1}
    # the forward pass's own: the sort by expert, its inverse, the token order
    assert discrete(policy, inside=False)["sort"] == 3
    # and a block that keeps nothing does all of it again
    assert discrete(jax.checkpoint_policies.nothing_saveable, inside=True) == {
        "sort": 3, "top_k": 1, "cumsum": 2}


@pytest.mark.parametrize("first, count, conditionals", [(0, 8, 0), (2, 2, 2)])
def test_a_layer_that_holds_every_expert_lowers_without_a_conditional(
        wide, first, count, conditionals):
    """``held == E``: the likely bound is the worst case and the program is
    the one from before the bound followed the load; with a share held
    there is one conditional forward and one in the backward pass, and no
    array of the worst-case rows leaves either (no residual of the branch
    not taken, written as zeros)."""
    p = wide["even"]

    def loss(u, w13):
        return share_of({**p, "u": u, "w13": w13}, first, count)[0].sum()

    found = list(_eqns(jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(
        p["u"], p["w13"]).jaxpr, "cond"))
    assert len(found) == conditionals
    worst = experts.row_bound_for(MANY * K)
    for eqn in found:
        assert all(worst not in v.aval.shape for v in eqn.outvars)
    text = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(
        p["u"], p["w13"]).as_text()
    assert text.count("stablehlo.case") == conditionals


@pytest.mark.parametrize("recomputed", [False, True])
def test_the_bias_is_handed_every_experts_excess_load(layer, recomputed):
    """The bias enters a top-k and has no gradient of the loss: what comes
    back in its place is each of ALL the experts' pairs over the even share,
    less 1, whatever the result's cotangent, from a recomputed layer too."""
    def out_of(bias, scale):
        return scale * share_of({**layer, "bias": bias}, 2, 2)[0].sum()

    if recomputed:
        out_of = jax.checkpoint(out_of)
    _, report = share_of(layer, 2, 2)
    chosen = np.bincount(np.asarray(report["sel"]).ravel(), minlength=E)
    want = chosen / (N * K / E) - 1.0
    for scale in (1.0, -3.0):
        got = np.asarray(jax.grad(out_of)(layer["bias"], scale))
        assert np.allclose(got, want, atol=1e-6)
    assert abs(want.sum()) <= 1e-6 and want.any()


def test_the_balancing_rule_evens_the_load(layer):
    """``b_e -= rate x excess_e`` step after step, the router fixed: a load
    that starts uneven by a third ends within two per cent of even,
    and stays there."""
    u = jax.random.normal(jax.random.PRNGKey(11), (4096, D))
    # logits of about unit spread, skewed towards the last experts
    w_gate = 0.25 * layer["w_gate"] + jnp.linspace(-0.25, 0.25, E)

    @jax.jit
    def step(bias):
        sel, _ = experts.route(u, w_gate, bias, K)
        excess = experts.excess_load(sel, E)
        return bias - 0.05 * excess, jnp.abs(excess).max()

    bias, worst = jnp.zeros((E,)), []
    for _ in range(60):
        bias, off = step(bias)
        worst.append(float(off))
    assert worst[0] > 0.25 and max(worst[-10:]) < 0.02


# -- the model against the reference ---------------------------------------------


@pytest.fixture(scope="module")
def batch():
    return jax.random.randint(jax.random.PRNGKey(1), (2, T + 1), 0, V)


@pytest.fixture(scope="module")
def params(batch):
    """Seeded parameters, the norm gains moved off their initial ones so that
    dropping or misplacing one shows."""
    p = model().init(jax.random.PRNGKey(0), batch, None, method="loss")
    flat = jax.tree_util.tree_leaves_with_path(p)
    keys = jax.random.split(jax.random.PRNGKey(2), len(flat))
    moved = [leaf + 0.1 * jax.random.normal(k, leaf.shape)
             if path[-1].key in ("norm1", "norm2", "q_norm", "k_norm")
             else leaf for (path, leaf), k in zip(flat, keys)]
    return jax.tree.unflatten(jax.tree.structure(p), moved)


@pytest.fixture(scope="module")
def want(params, batch):
    """The reference's loss, gradients and logits on ``params``, FREELY
    routed, once."""
    value, aux, grads = jax.jit(
        lambda p, x: ref.loss_and_grads(p, x, CFG))(params, batch)
    return float(value), jax.tree.leaves(grads), jax.jit(
        lambda p, x: ref.forward(p, x, CFG))(params, batch[:, :-1]), aux


def gaps(m, p, x, want):
    want_loss, want_grads, want_logits, _ = want

    @jax.jit
    def run(p, x):
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(
                lambda q: m.apply(q, x, None, True, method="loss"),
                has_aux=True)(p), m.apply(p, x[:, :-1])

    ((loss, aux), grads), logits = run(p, x)
    worst = max(
        float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b), 1e-20))
        for a, b in zip(jax.tree.leaves(grads), want_grads))
    return (abs(float(loss) - want_loss),
            float(jnp.abs(logits - want_logits).max()
                  / jnp.abs(want_logits).max()), worst), aux


def test_system_against_the_reference(params, batch, want):
    (loss_gap, logits_gap, grads_gap), aux = gaps(model(), params, batch, want)
    assert loss_gap <= 1e-5 and logits_gap <= 1e-5 and grads_gap <= 1e-5
    # the same choice, token by token and layer by layer; nothing dropped
    assert aux["routing"].shape == (4, 2, T, 2)
    assert bool((jnp.sort(aux["routing"], -1)
                 == jnp.sort(want[3]["selection"], -1)).all())
    assert float(aux["pairs_dropped"]) == 0
    assert float(aux["expert_load"].sum()) == float(
        ((aux["routing"] >= 2) & (aux["routing"] < 4)).sum())


def test_the_reference_takes_the_routing_it_is_given(params, batch, want):
    """Under its own free selection the forced reference is the free one;
    under another it is not; its margins are the gaps between the k-th and
    the (k+1)-th biased score."""
    free = want[3]["selection"]
    value, aux, _ = ref.loss_and_grads(params, batch, CFG, routing=free)
    assert abs(float(value) - want[0]) <= 1e-6
    other = (free + 1) % 8
    moved, aux2, _ = ref.loss_and_grads(params, batch, CFG, routing=other)
    assert abs(float(moved) - want[0]) > 1e-5
    assert aux["margin"].shape == (4, 2, T) and bool((aux["margin"] >= 0).all())
    # the FIRST expert layer's inputs do not depend on the routing
    assert bool((aux2["selection"][0] == free[0]).all())


@pytest.mark.parametrize("form", [
    {"remat": False}, {"loss_chunk": 0}, {"attn_impl": "flash"}])
def test_forms_agree(params, batch, want, form):
    (loss_gap, logits_gap, grads_gap), _ = gaps(
        model(**form), params, batch, want)
    assert loss_gap <= 1e-5 and logits_gap <= 1e-5 and grads_gap <= 1e-5


def _route_mutant(kind):
    def route(u, w_gate, bias, top_k, scaling=1.0, scoring="sigmoid", **_):
        logits = jnp.dot(u.astype(jnp.float32), w_gate,
                         precision=jax.lax.Precision.HIGHEST)
        scores = (jax.nn.softmax(logits, axis=-1) if kind == "softmax"
                  else jax.nn.sigmoid(logits))
        _, sel = jax.lax.top_k(
            scores if kind == "unbiased_top_k" else scores + bias, top_k)
        w = jnp.take_along_axis(
            scores + bias if kind == "bias_in_the_weights" else scores, sel,
            axis=-1)
        if kind == "normalised_over_held_only":
            w = jnp.where((sel >= 2) & (sel < 4), w, 0.0)
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6) * scaling
        return sel.astype(jnp.int32), w
    return route


def _b_and_c_swapped(params):
    def swap(path, leaf):
        if path[-1].key != "in_proj":
            return leaf
        b, c, x = jnp.split(leaf, 3, axis=1)
        return jnp.concatenate([x, c, b], axis=1)  # x takes B's place too
    return jax.tree_util.tree_map_with_path(swap, params)


class NoQKNorm(RoutedHybridLM):
    """q and k go to RoPE as the projections give them."""

    def _attention(self, w, y, **layer):
        return HybridLM._attention(self.clone(qk_norm=False), w, y, **layer)


ROUTE_MUTATIONS = ("bias_in_the_weights", "unbiased_top_k",
                   "normalised_over_held_only", "softmax")
MUTATIONS = ROUTE_MUTATIONS + ("b_and_c_swapped", "rope_left_out",
                               "qk_norm_left_out", "theta_10000")


@pytest.mark.parametrize("mutation", MUTATIONS)
def test_a_mutation_fails_the_comparison(params, batch, want, mutation,
                                         monkeypatch):
    """Each departure from the published layer moves the loss, the logits or
    a gradient by 1e-3 and more: a hundred times the right program's gap."""
    m, p = model(), params
    if mutation in ROUTE_MUTATIONS:
        monkeypatch.setattr(experts, "route", _route_mutant(mutation))
    elif mutation == "b_and_c_swapped":
        p = _b_and_c_swapped(params)
    elif mutation == "rope_left_out":
        m = model(rope_theta=0.0)
    elif mutation == "theta_10000":
        m = model(rope_theta=10000.0)
    else:
        m = model(NoQKNorm)
    assert max(gaps(m, p, batch, want)[0]) >= 1e-3


# -- what the model says of itself ---------------------------------------------


def _published():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2-8b-a1b.json")) as f:
        return json.load(f)


def test_the_parameter_tree_is_the_stage_of_the_published_model():
    """Published layers 1-5 (conv + dense SwiGLU; full_attention, conv,
    conv, conv + experts 0-7 of 32), a quarter of the vocabulary:
    507,820,288 parameters (ISSUE 34: 507.8 M)."""
    config = _published()
    big = RoutedHybridLM.from_config(config, **config["model"]["kwargs"])
    assert big.layer_types == ("conv", "attention", "conv", "conv", "conv")
    assert big.ffn_kinds == ("dense",) + ("experts",) * 4
    shapes = jax.eval_shape(
        lambda r, s: big.init(r, s, None, method="loss"), jax.random.PRNGKey(0),
        jax.ShapeDtypeStruct((1, 65), jnp.int32))["params"]
    assert shapes["layer_0"]["w_in"].shape == (2048, 2 * 7168)
    assert shapes["layer_0"]["in_proj"].shape == (2048, 3 * 2048)
    assert shapes["layer_0"]["conv_w"].shape == (3, 2048)
    assert shapes["layer_1"]["wk"].shape == (2048, 8 * 64)
    assert shapes["layer_1"]["q_norm"].shape == (64,)
    for i in range(1, 5):
        layer = shapes[f"layer_{i}"]
        assert layer["router"].shape == (2048, 32)
        assert layer["expert_bias"].shape == (32,)
        assert layer["w13"].shape == (8, 2048, 2 * 1792)
        assert layer["w2"].shape == (8, 1792, 2048)
        assert "w_in" not in layer
    count = lambda tree: sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    assert count(shapes["layer_0"]) == 60_823_552 + 2 * 2048
    assert count(shapes) == 507_820_288
    assert {a.dtype for a in jax.tree.leaves(shapes)} == {jnp.dtype("float32")}


def test_from_config_refuses_what_the_model_does_not_build():
    for key, value in (("conv_bias", True), ("norm_topk_prob", False),
                       ("use_expert_bias", False)):
        with pytest.raises(ValueError, match=key):
            RoutedHybridLM.from_config({**CONFIG, key: value})
    with pytest.raises(ValueError, match="model_type"):
        HybridLM.from_config({**CONFIG, "model_type": "lfm2"})
    with pytest.raises(ValueError, match="layer_types gives"):
        HybridLM.from_config({**CONFIG, "layer_types": ["conv", "mamba"] * 4})
    with pytest.raises(ValueError, match="not an expert layer's share"):
        model(first_expert=7).init(
            jax.random.PRNGKey(0), jnp.zeros((1, 9), jnp.int32), None,
            method="loss")
    # the first family's experts are another mechanism, and the refusal says so
    with pytest.raises(ValueError, match="shared expert beside routed"):
        HybridLM.from_config({
            "model_type": "granitemoehybrid", "num_local_experts": 8})
    assert RoutedHybridLM.from_config(CONFIG).first_expert == 2


@pytest.mark.parametrize("sizes", ["published", "tiny"])
def test_the_models_flops_are_the_benchmarks_count(sizes):
    """``fit_facts``'s ``flops_per_row`` (what the live ``estimator.mfu``
    counts) is ``benchmark/harness/moe_costs.step_flops``'s total, part by
    part, with the experts at the uniform share; hand-worked at the
    published widths (ISSUE 34: 1.30e9 model FLOPs a token)."""
    from benchmark.harness import moe_costs

    config, t = (_published(), 8192) if sizes == "published" else (CONFIG, T)
    module = HybridLM.from_config(config)
    parts = moe_costs.step_flops(config, 1, t)
    assert module.flops_per_row_parts(t) == {
        **{k: v for k, v in parts.items() if k != "total"}, "scan": 0}
    facts = module.fit_facts(np.zeros((1, t + 1), np.int32))
    assert facts["flops_per_row"] == parts["total"]
    assert facts["experts.flops_per_row"] == parts["experts"]
    assert facts["experts.flops_counted"] == "uniform share"
    if sizes == "tiny":
        return
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    hand = (6 * (4 * conv + attention + 3 * 2048 * 7168 + 4 * 2048 * 32) * t
            + 4 * 6 * 3 * 2048 * 1792 * (t * 4 * 8 // 32)
            + 12 * 2048 * (t * (t + 1) // 2) + 6 * 2048 * 16384 * t)
    assert parts["total"] == hand
    assert hand / t == pytest.approx(1.30e9, rel=0.01)
    assert (facts["experts.held"], facts["experts.total"],
            facts["experts.per_token"], facts["experts.layers"]) == (8, 32, 4, 4)
    assert facts["layer_kinds.conv"] == 4 and facts["layer_kinds.attention"] == 1
    assert facts["experts.rows_per_row"] == 8192 * 4
    assert facts["remat_keeps"] == "mlp_out,experts_perm"


def test_epoch_facts_are_the_loads_own_words():
    m = model()
    said = m.epoch_facts({"expert_load": np.array([[30.0, 10.0], [20.0, 20.0]]),
                          "pairs_dropped": np.array(0.0),
                          "layers_at_full_bound": np.array(0.0)}, steps=2)
    assert said["counters"] == {"experts.pairs_held": 80.0,
                                "experts.pairs_dropped": 0.0,
                                "experts.layers_at_full_bound": 0.0,
                                "experts.steps_reported": 2}
    assert said["gauges"]["experts.load_max_over_mean"] == pytest.approx(1.25)
    assert said["gauges"]["experts.pairs_held_per_step"] == 40.0
    assert HybridLM(vocab_size=8).epoch_facts({}, 3) == {}


def test_epoch_facts_count_the_layers_that_ran_at_the_full_bound():
    """A summed report of 3 steps in which 2 of the 4 x 3 layer-steps
    overflowed: the counter's increment and the newest epoch's share."""
    m = model()
    assert m.train_report == ("expert_load", "pairs_dropped",
                              "layers_at_full_bound")
    said = m.epoch_facts({"expert_load": np.full((4, 2), 30.0),
                          "pairs_dropped": np.array(0.0),
                          "layers_at_full_bound": np.array(2.0)}, steps=3)
    assert said["counters"]["experts.layers_at_full_bound"] == 2.0
    assert said["gauges"]["experts.likely_bound_share"] == pytest.approx(
        1 - 2 / 12)
    facts = m.fit_facts(np.zeros((1, T + 1), np.int32))
    assert facts["experts.rows_likely_per_row"] == m.expert_likely_row_bound(T)
    published = RoutedHybridLM.from_config(_published())
    assert published.expert_row_bound(32768) == 131072
    assert published.expert_likely_row_bound(32768) == experts.row_bound_for(
        int(experts.SLACK * 32768))


def test_the_facts_count_what_the_token_side_gathers_and_keeps():
    """``experts.token_rows_gathered_per_pass`` is the rows one token-side
    sum gathers for a batch row at the likely bound, in the form the shapes
    choose; ``remat_kept_bytes_per_row`` counts the kept discrete part, five
    int32 a (token, choice) pair and one a token a layer."""
    published = RoutedHybridLM.from_config(
        _published(), **_published()["model"]["kwargs"])
    facts = published.fit_facts(np.zeros((1, 8193), np.int32))
    assert facts["experts.rows_likely_per_row"] == 10240
    assert facts["experts.token_rows_gathered_per_pass"] == 10240 + 8192
    kept = published._remat_keeps(8192)
    assert kept[experts.KEPT] == 4 * 4 * (5 * 8192 * 4 + 8192)
    assert facts["remat_kept_bytes_per_row"] == sum(kept.values())
    # every expert held: the buffer is tokens x k rows, a gather a choice
    whole = published.clone(experts_held=32, first_expert=0)
    assert whole.fit_facts(np.zeros((1, 8193), np.int32))[
        "experts.token_rows_gathered_per_pass"] == 8192 * 4


# -- the estimator -------------------------------------------------------------

HYPER = {"learning_rate": 3e-4, "b1": 0.9, "b2": 0.95, "weight_decay": 0.1,
         "warmup_steps": 4, "expert_bias_rate": 0.02}


def _session_fit(name, ids, est_kw, held_rows=0):
    """A JaxEstimator fit on a frame that came through the ETL, on one
    device (the resident scan runner), float32 / highest."""
    from jax.sharding import Mesh

    import raydp_tpu
    from raydp_tpu.cluster import api as cluster
    from raydp_tpu.estimator import JaxEstimator

    table = pa.table({"tokens": pa.FixedSizeListArray.from_arrays(
        pa.array(ids.ravel()), ids.shape[1])})
    session = raydp_tpu.init_etl(name, num_executors=1, executor_cores=1,
                                 executor_memory="500M")
    try:
        df = session.from_arrow(table, num_partitions=2)
        est = JaxEstimator(
            model=model(), loss="model", feature_columns=["tokens"],
            feature_dtype=np.int32, label_column=None, batch_size=2,
            mesh=Mesh(np.array(jax.devices()[:1]), ("data",)), **est_kw)
        with jax.default_matmul_precision("highest"):
            history = est.fit_on_etl(
                df.limit(len(ids) - held_rows),
                df.limit(held_rows) if held_rows else None)
    finally:
        raydp_tpu.stop_etl()
        cluster.shutdown()
    return est, history


def _value(snap, name):
    return snap.get(name, {"value": 0.0})["value"]


def test_estimator_fit_lowers_held_out_loss_and_reports_the_training_load():
    """ETL -> store -> exchange -> JaxEstimator.fit(loss="model"), no
    estimator argument of its own: the loss falls, and the experts' load of
    the TRAINING steps (summed inside the epoch program, fetched at the
    epoch's fence) is in the history, the counters and the gauges."""
    from raydp_tpu import obs

    motif = np.random.default_rng(3).integers(0, V, 4)
    ids = np.tile(motif, (12, (T + 1) // 4 + 1))[:, :T + 1].astype(np.int32)
    before = obs.metrics.snapshot()
    est, history = _session_fit(
        "routedlm", ids, dict(
            optimizer=hybridlm_optimizer(3e-3, expert_bias_rate=0.02),
            num_epochs=3, seed=0), held_rows=4)
    assert history[-1]["eval_loss"] < history[0]["eval_loss"] - 0.1
    assert est.fit_stats_["runner"] == "resident_scan"
    assert est.fit_stats_["steps"] == 3 * 4
    snap = obs.metrics.snapshot()
    held = sum(float(rec["train_report"]["expert_load"].sum())
               for rec in history)
    assert all(rec["train_report"]["expert_load"].shape == (4, 2)
               for rec in history)
    assert 0 < held < 12 * 2 * T * 2 * 4  # some of the pairs, not all
    for name, value in (("pairs_held", held), ("pairs_dropped", 0.0),
                        ("steps_reported", 12.0)):
        key = f"model.experts.{name}"
        assert _value(snap, key) - _value(before, key) == value, name
    last = history[-1]["train_report"]["expert_load"]
    assert snap["model.experts.load_max_over_mean"]["value"] == pytest.approx(
        float((last.max(1) / np.maximum(last.mean(1), 1e-9)).mean()))
    assert snap["model.experts.pairs_held_per_step"]["value"] == last.sum() / 4
    for gauge, fact in (("experts.held", 2), ("experts.total", 8),
                        ("experts.per_token", 2), ("experts.layers", 4),
                        ("layer_kinds.conv", 4), ("layer_kinds.attention", 1),
                        ("experts.rows_per_row", experts.ROW_TILE)):
        assert snap[f"model.{gauge}"]["value"] == fact, gauge
    # the evaluation's own report keeps its gauges
    assert "estimator.eval.expert_load.0" in snap
    assert history[-1]["eval_pairs_dropped"] == [0.0]
    # expert_bias moves by the balancing rule alone: every step's excess
    # loads sum to zero over the experts, so the biases' sum stays
    start = model().init(jax.random.PRNGKey(0), ids[:2], None, method="loss")
    fitted = est.get_model().params
    for i in range(1, 5):
        a = np.asarray(start["params"][f"layer_{i}"]["expert_bias"])
        moved = np.asarray(fitted["params"][f"layer_{i}"]["expert_bias"]) - a
        assert np.abs(moved).max() > 1e-3 and abs(moved.sum()) <= 1e-5
        assert not np.array_equal(
            np.asarray(start["params"][f"layer_{i}"]["router"]),
            np.asarray(fitted["params"][f"layer_{i}"]["router"]))


def _replay(ids, order, seed=5, **changed):
    start = model().init(jax.random.PRNGKey(seed), ids[:2], None, method="loss")
    treedef = jax.tree.structure(start)
    first = [np.asarray(a) for a in jax.tree.leaves(start)]
    leaves = [a.copy() for a in first]  # the reference's AdamW works in place
    state, losses = ref.adamw_init(leaves), []
    hyper = {**HYPER, **changed}
    for i in range(0, len(order), 2):
        value, _, grads = ref.loss_and_grads(
            jax.tree.unflatten(treedef, leaves), ids[order[i:i + 2]], CFG)
        losses.append(float(value))
        leaves, state = ref.adamw_step(
            leaves, [np.asarray(g) for g in jax.tree.leaves(grads)], state,
            hyper["learning_rate"], hyper["b1"], hyper["b2"],
            hyper["weight_decay"], hyper["warmup_steps"],
            hyper["expert_bias_rate"], ref.bias_leaves(start))
    return float(np.mean(losses)), first, leaves


def _change_gap(got, first, want):
    return max(
        float(np.linalg.norm((g - z) - (w - z))
              / max(np.linalg.norm(w - z), 1e-20))
        for g, z, w in zip(got, first, want) if (w - z).any() or (g - z).any())


@pytest.fixture(scope="module")
def fitted_epoch():
    ids = np.random.default_rng(7).integers(0, V, (4, T + 1)).astype(np.int32)
    est, history = _session_fit(
        "routedlm-step", ids, dict(optimizer=hybridlm_optimizer(**HYPER),
                                   num_epochs=1, seed=5))
    assert est.fit_stats_["steps"] == 2
    return ids, est, history[0]["train_loss"], [
        np.asarray(a) for a in jax.tree.leaves(est.get_model().params)]


def test_the_epoch_program_is_the_references_epoch(fitted_epoch):
    """What the timed path itself produces (make_train_step in the scan
    runner, donation, hybridlm_optimizer with its warm-up and the balancing
    rule, the epoch's order) against the reference's gradients (freely
    routed: at float32 / highest the choices are the same) through the
    reference's float32 optimizer."""
    ids, est, loss, got = fitted_epoch
    order = est.epoch_order(0, len(ids))
    ref_loss, first, want = _replay(ids, order)
    assert abs(loss - ref_loss) <= 1e-5
    assert _change_gap(got, first, want) <= 2e-3


@pytest.mark.parametrize("wrong", [
    {"learning_rate": 3.3e-4}, "order", {"warmup_steps": 0},
    {"expert_bias_rate": 0.022}])
def test_a_wrong_update_fails_the_comparison(fitted_epoch, wrong):
    ids, est, _, got = fitted_epoch
    order = est.epoch_order(0, len(ids))
    if wrong == "order":
        _, first, want = _replay(ids, order[::-1])
    else:
        _, first, want = _replay(ids, order, **wrong)
    assert _change_gap(got, first, want) > 4e-3
