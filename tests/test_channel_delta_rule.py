"""``ops.delta_rule.channel_gated_delta_rule`` (a decay a CHANNEL of the key:
Kimi Delta Attention's rule; a Pallas kernel forward and one backward, run
here through the interpreter) against the recurrence token by token, forward
and all five gradients: chunks of 16 and 64 tokens (one sub-block a chunk,
and four, whose products go through a sub-block's middle row), log-decays at
the bound (-5 every token and channel: -320 over a chunk, where a form
factorised over the chunk overflows), no decay, one grid step a head and two
(the state crosses grid steps), heads of 128 x 128, bf16 operands, a mesh;
and against the recurrence in float64 where a running sum is large and the
next tokens' decays small."""

import jax
import jax.numpy as jnp
import pytest
from ling_hybrid_model import no_persistent_cache  # noqa: F401 - autouse

from raydp_tpu.ops import delta_rule

B, T, H, DK, DV = 2, 256, 4, 16, 24


def recurrence(q, k, v, log_alpha, beta):
    """Decay a column, erase, write, read: a token at a time."""
    def token(state, inputs):
        q_t, k_t, v_t, a_t, b_t = inputs
        state = state * jnp.exp(a_t)[:, :, None, :]
        held = jnp.einsum("bhvd,bhd->bhv", state, k_t)
        state = state + (b_t[..., None] * (v_t - held))[..., None] * (
            k_t[:, :, None, :])
        return state, jnp.einsum("bhvd,bhd->bhv", state, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros(q.shape[:1] + (q.shape[2], v.shape[-1], q.shape[-1])),
        tuple(z.swapaxes(0, 1) for z in (q, k, v, log_alpha, beta)))
    return o.swapaxes(0, 1)


@pytest.fixture(scope="module")
def operands():
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    return {
        "q": unit(jax.random.normal(keys[0], (B, T, H, DK))) * DK ** -0.5,
        "k": unit(jax.random.normal(keys[1], (B, T, H, DK))),
        "v": jax.random.normal(keys[2], (B, T, H, DV)),
        "beta": jax.nn.sigmoid(jax.random.normal(keys[3], (B, T, H))),
        "weight": jax.random.normal(keys[4], (B, T, H, DV)),
        "decays": {
            "mixed": -5.0 * jax.nn.sigmoid(
                3.0 * jax.random.normal(keys[5], (B, T, H, DK))),
            "at_the_bound": jnp.full((B, T, H, DK), -5.0),
            "none": jnp.zeros((B, T, H, DK))}}


def both(rule, o, log_alpha, tokens=T):
    """(o, the five gradients) of ``rule`` on the first ``tokens`` tokens."""
    def loss(q, k, v, a, b):
        return (rule(q, k, v, a, b) * o["weight"][:, :tokens]).sum()

    args = tuple(x[:, :tokens] for x in (
        o["q"], o["k"], o["v"], log_alpha, o["beta"]))
    with jax.default_matmul_precision("highest"):
        return rule(*args), jax.grad(loss, argnums=(0, 1, 2, 3, 4))(*args)


def close(got_o, got_g, want_o, want_g, value=1e-5, gradient=2e-4):
    assert bool(jnp.isfinite(got_o).all())
    assert float(jnp.abs(got_o - want_o).max()
                 / jnp.abs(want_o).max()) < value
    for name, got, want in zip("q k v log_alpha beta".split(), got_g, want_g):
        assert got.shape == want.shape and got.dtype == want.dtype, name
        assert bool(jnp.isfinite(got).all()), name
        gap = float(jnp.abs(got - want).max() / (jnp.abs(want).max() + 1e-30))
        assert gap < gradient, (name, gap)


# (decay, chunk, tokens): 128 tokens are ONE grid step a head (8 chunks of 16,
# 2 of 64), 256 in chunks of 16 are TWO (``CHUNKS_A_STEP`` = 8 a step): the
# state, and in the backward pass its gradient, cross from step to step
@pytest.mark.parametrize("decay, chunk, tokens", [
    ("mixed", 16, 128), ("mixed", 64, 128), ("mixed", 16, 256),
    ("at_the_bound", 16, 256), ("at_the_bound", 64, 128), ("none", 64, 256)])
def test_the_chunked_form_is_the_recurrence(operands, decay, chunk, tokens):
    log_alpha = operands["decays"][decay]
    *_, tile, heads = delta_rule._layout(
        operands["q"][:, :tokens].reshape(B, tokens, H * DK),
        operands["v"][:, :tokens].reshape(B, tokens, H * DV),
        operands["beta"][:, :tokens], chunk)
    assert heads == 2  # the four heads go two a grid step, side by side
    assert tokens // tile == (2 if (chunk, tokens) == (16, 256) else 1)
    want_o, want_g = both(recurrence, operands, log_alpha, tokens)
    got_o, got_g = both(
        lambda *a: delta_rule.channel_gated_delta_rule(*a, chunk=chunk),
        operands, log_alpha, tokens)
    close(got_o, got_g, want_o, want_g)


@pytest.fixture(scope="module")
def wide():
    """One head of 128 x 128 (the Ling cell's; a lone head is a grid step of
    its own), 128 tokens."""
    keys = jax.random.split(jax.random.PRNGKey(1), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    shape = (1, 128, 1, 128)
    return {
        "q": unit(jax.random.normal(keys[0], shape)) * 128 ** -0.5,
        "k": unit(jax.random.normal(keys[1], shape)),
        "v": jax.random.normal(keys[2], shape),
        "beta": jax.nn.sigmoid(jax.random.normal(keys[3], shape[:3])),
        "weight": jax.random.normal(keys[4], shape),
        "log_alpha": -5.0 * jax.nn.sigmoid(
            3.0 * jax.random.normal(keys[5], shape))}


def test_heads_of_128_by_128_are_the_recurrence(wide):
    want_o, want_g = both(recurrence, wide, wide["log_alpha"], 128)
    got_o, got_g = both(delta_rule.channel_gated_delta_rule, wide,
                        wide["log_alpha"], 128)
    close(got_o, got_g, want_o, want_g)


def test_bf16_operands_keep_float32_decays_and_state_in_both_passes(wide):
    """bf16 q, k, v (and ``do``): ``o`` and their gradients come back bf16,
    the decay's and beta's float32, all within bf16's rounding of the
    float32 recurrence."""
    want_o, want_g = both(recurrence, wide, wide["log_alpha"], 128)
    low = {name: x.astype(jnp.bfloat16) if name in ("q", "k", "v", "weight")
           else x for name, x in wide.items()}
    got_o, got_g = both(delta_rule.channel_gated_delta_rule, low,
                        low["log_alpha"], 128)
    assert [g.dtype for g in got_g] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    assert got_o.dtype == jnp.bfloat16
    close(got_o.astype(jnp.float32),
          [g.astype(jnp.float32) for g in got_g], want_o, want_g, 3e-2, 5e-2)


def test_the_backward_passes_states_are_the_forward_passes(operands):
    """The backward call runs the state alone through the chunks again (W
    S^T without the read-out's rows): the states it takes its gradients at
    are the forward call's bit for bit, and the recurrence's."""
    o = operands
    q, k, v, log_alpha, beta = (
        x[0, :, 0] for x in (o["q"], o["k"], o["v"], o["decays"]["mixed"],
                             o["beta"][..., None]))
    chunk = 64

    def by_chunk(x):
        return x.reshape(T // chunk, chunk, x.shape[-1])

    with jax.default_matmul_precision("highest"):
        m = delta_rule._chunk_matrices(
            *(by_chunk(x) for x in (q, k, v, log_alpha, beta)), delta_rule.SUB)
        forward = backward = jnp.zeros((DV, DK))
        for j in range(T // chunk):
            _, forward, _ = delta_rule._chunk_state(
                m["u0"][j], jnp.concatenate([m["w"][j], m["qg"][j]]),
                m["k_end"][j], m["at_end"][j], forward, m["qk"][j])
            _, backward, _ = delta_rule._chunk_state(
                m["u0"][j], m["w"][j], m["k_end"][j], m["at_end"][j],
                backward)
            assert bool((forward == backward).all()), j

        def token(state, x):
            k_t, v_t, a_t, b_t = x
            state = state * jnp.exp(a_t)
            return state + (b_t * (v_t - state @ k_t))[:, None] * k_t, None

        want, _ = jax.lax.scan(token, jnp.zeros((DV, DK)),
                               (k, v, log_alpha, beta))
    assert float(jnp.abs(forward - want).max() / jnp.abs(want).max()) < 1e-5


def _equations(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def test_state_decays_and_inverse_are_float32_under_bf16_operands(wide):
    """Read from the two calls' kernels as traced for bf16 operands: the
    state (and its gradient) is float32 scratch; every exponential is taken
    in float32; every product accumulates in float32; a product of float32
    operands (the inverse's own, the sums over a row) asks for the MXU's
    float32 passes; every other product's operands are bf16 (the operands'
    dtype: the three exact bfloat16 passes of the log-decays' sums among
    them)."""
    low = [wide[n].astype(jnp.bfloat16) for n in "qkv"] + [
        wide["log_alpha"], wide["beta"]]
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda *a: delta_rule.channel_gated_delta_rule(*a).astype(
            jnp.float32).sum(), argnums=(0, 1, 2, 3, 4)))(*low)
    found = {eqn.params["name"]: eqn for eqn in _equations(jaxpr.jaxpr)
             if eqn.primitive.name == "pallas_call"}
    assert set(found) == {"delta_rule_fwd", "delta_rule_bwd"}
    for name, eqn in found.items():
        kernel = eqn.params["jaxpr"]
        scratch = [v.aval for v in kernel.invars[
            -eqn.params["grid_mapping"].num_scratch_operands:]]
        states = 1 if name == "delta_rule_fwd" else 2
        assert [(a.shape, a.dtype) for a in scratch[:states]] == [
            ((1, 128, 128), jnp.float32)] * states, name
        products = exponentials = 0
        for inner in _equations(kernel):
            if inner.primitive.name == "exp":
                exponentials += 1
                assert inner.invars[0].aval.dtype == jnp.float32
            if inner.primitive.name != "dot_general":
                continue
            products += 1
            assert inner.outvars[0].aval.dtype == jnp.float32
            dtypes = {v.aval.dtype for v in inner.invars}
            assert len(dtypes) == 1, dtypes
            if dtypes == {jnp.dtype(jnp.float32)}:
                assert inner.params["precision"] == (
                    jax.lax.Precision.HIGHEST,) * 2
            else:
                assert dtypes == {jnp.dtype(jnp.bfloat16)}
        assert products and exponentials, name


def test_under_a_mesh_each_device_runs_the_kernel_on_its_rows(operands):
    """``ops/interaction.py``'s rule: XLA cannot partition a Mosaic call,
    so under a mesh that splits the batch the calls go through
    ``shard_map``."""
    from raydp_tpu.parallel import make_mesh

    o, log_alpha = operands, operands["decays"]["mixed"]
    want_o, want_g = both(delta_rule.channel_gated_delta_rule, o, log_alpha,
                          128)
    with jax.set_mesh(make_mesh({"data": 2}, jax.devices()[:2])):
        got_o, got_g = both(delta_rule.channel_gated_delta_rule, o, log_alpha,
                            128)
    close(got_o, got_g, want_o, want_g, 1e-6, 1e-6)
def test_a_decay_alike_on_every_channel_is_the_rule_with_a_decay_a_head(
        operands):
    """Where a head's channels all decay alike, the rule IS
    ``gated_delta_rule``'s (another algebra: the decay outside the chunk's
    contraction)."""
    log_alpha = -jax.nn.softplus(jax.random.normal(
        jax.random.PRNGKey(7), (B, T, H)))
    o = operands
    with jax.default_matmul_precision("highest"):
        a_head = delta_rule.gated_delta_rule(
            o["q"], o["k"], o["v"], log_alpha, o["beta"])
        a_channel = delta_rule.channel_gated_delta_rule(
            o["q"], o["k"], o["v"],
            jnp.broadcast_to(log_alpha[..., None], (B, T, H, DK)), o["beta"])
    assert float(jnp.abs(a_head - a_channel).max()) < 1e-5


def test_an_exponent_is_rounded_at_its_own_size_not_the_running_sums(operands):
    """40 tokens of a chunk at the floor (the running sum passes -200) and
    then 24 slow ones, against the recurrence IN FLOAT64: an exponent formed
    as a difference of two running sums is rounded at the sums' size (the
    form read 5.7e-6 to 7.7e-6 here while it did that), the sum of the
    log-decays between two tokens at its own (1.7e-7 to 2.4e-7)."""
    import numpy as np

    o, tokens = operands, 128
    slow = -0.01 * operands["decays"]["mixed"][:, :tokens]
    log_alpha = jnp.where((jnp.arange(tokens) % 64 < 40)[None, :, None, None],
                          -5.0, slow)
    args = [x[:, :tokens] for x in (o["q"], o["k"], o["v"])] + [
        log_alpha, o["beta"][:, :tokens]]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(delta_rule.channel_gated_delta_rule(*args), np.float64)
    q, k, v, a, beta = (np.asarray(x, np.float64) for x in args)
    state, want = np.zeros((B, H, DV, DK)), np.zeros((B, tokens, H, DV))
    for t in range(tokens):
        state = state * np.exp(a[:, t])[:, :, None, :]
        held = np.einsum("bhvd,bhd->bhv", state, k[:, t])
        state = state + (beta[:, t][..., None] * (v[:, t] - held))[
            ..., None] * k[:, t][:, :, None, :]
        want[:, t] = np.einsum("bhvd,bhd->bhv", state, q[:, t])
    assert np.abs(got - want).max() / np.abs(want).max() < 1e-6


def test_lengths_that_do_not_divide_are_refused(operands):
    o = operands
    with pytest.raises(ValueError, match="does not divide"):
        delta_rule.channel_gated_delta_rule(
            o["q"], o["k"], o["v"], o["decays"]["none"], o["beta"], chunk=48)
    with pytest.raises(ValueError, match="a power of two times"):
        delta_rule.channel_gated_delta_rule(
            o["q"], o["k"], o["v"], o["decays"]["none"], o["beta"],
            chunk=32, sub=12)


def test_the_scan_lies_under_its_scope_and_names_its_result():
    from raydp_tpu.obs import profiler

    def f(q, k, v, a, b):
        return delta_rule.channel_gated_delta_rule(q, k, v, a, b)

    shapes = [jax.ShapeDtypeStruct(s, jnp.float32) for s in (
        (1, 64, 2, 8), (1, 64, 2, 8), (1, 64, 2, 8), (1, 64, 2, 8), (1, 64, 2))]
    text = jax.jit(f).lower(*shapes).compile().as_text()
    chains = [tuple(said["scopes"])
              for said in profiler.scopes_in_text(text).values()]
    assert any(delta_rule.SCOPE in c for c in chains)
    assert delta_rule.SAVED_OUTPUT in str(jax.make_jaxpr(f)(*shapes))
