"""The fused head product + cross-entropy (models/looplm.chunked_cross_entropy)
at tiny sizes, float32: its own backward pass against autodiff of the plain
whole-logits form, and that a gradient of either language model's loss
computes every chunk's logits once."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from raydp_tpu.models import HybridLM, LoopLM
from raydp_tpu.models.looplm import chunked_cross_entropy

B, T, D, V = 2, 32, 16, 96


def plain(h, w, contract, targets, scale, weight):
    """All the logits at once, autodiff's backward pass."""
    z = scale * jnp.tensordot(h, w, ((h.ndim - 1,), (contract,)))
    ce = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(
        z, targets[..., None], axis=-1)[..., 0]
    if weight is None:
        weight = jnp.full(targets.shape, 1.0 / targets.size)
    return jnp.sum(weight * ce), ce


def inputs(layout, weighted):
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    h = jax.random.normal(keys[0], (B, T, D))
    w = jax.random.normal(keys[1], (D, V) if layout == "DV" else (V, D))
    targets = jax.random.randint(keys[2], (B, T), 0, V)
    weight = jax.random.uniform(keys[3], (B, T)) / (B * T) if weighted else None
    return h, w, (0 if layout == "DV" else 1), targets, weight


def close(got, want):
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5 * float(
        jnp.abs(want).max()))


@pytest.mark.parametrize("chunk", [16, 24, 0, 4096],
                         ids=["divides", "does_not_divide", "zero", "larger"])
@pytest.mark.parametrize("weighted", [True, False], ids=["weights", "mean"])
@pytest.mark.parametrize("scale", [1.0, 0.125])
@pytest.mark.parametrize("layout", ["DV", "VD"])
def test_its_backward_pass_is_autodiffs(layout, scale, weighted, chunk):
    """Loss, per-token cross-entropy, and the gradients to the hidden state,
    to the head in its own layout and to the weights, under an upstream
    cotangent that is not 1; and the primal alone gives the same loss."""
    h, w, contract, targets, weight = inputs(layout, weighted)
    args = (h, w, weight) if weighted else (h, w)

    def fused(h, w, weight=None):
        total, ce = chunked_cross_entropy(
            h, w, contract, targets, chunk, "test", scale, weight)
        return 3.0 * total, ce

    def whole(h, w, weight=None):
        total, ce = plain(h, w, contract, targets, scale, weight)
        return 3.0 * total, ce

    argnums = tuple(range(len(args)))
    with jax.default_matmul_precision("highest"):
        (loss, ce), grads = jax.value_and_grad(
            fused, argnums, has_aux=True)(*args)
        (want, want_ce), want_grads = jax.value_and_grad(
            whole, argnums, has_aux=True)(*args)
        primal, _ = fused(*args)
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    assert float(primal) == pytest.approx(float(loss), rel=1e-6)
    close(ce, want_ce)
    for got, wanted in zip(grads, want_grads):
        assert got.shape == wanted.shape and got.dtype == wanted.dtype
        close(got, wanted)


@pytest.mark.parametrize("layout", ["DV", "VD"])
def test_inside_a_scan_under_jit(layout):
    """Called from the body of a ``lax.scan`` over steps (as a loss inside
    an epoch's scan of training steps is), the whole under ``jit``: the sum
    over steps and its gradients are autodiff's."""
    h, w, contract, targets, weight = inputs(layout, True)

    def over_steps(loss_of):
        def run(h, w, weight):
            def step(total, k):
                value, _ = loss_of(h * k, w, contract, targets, weight)
                return total + value, None

            return lax.scan(step, 0.0, jnp.arange(1.0, 4.0))[0]

        return jax.jit(jax.value_and_grad(run, (0, 1, 2)))

    with jax.default_matmul_precision("highest"):
        loss, grads = over_steps(
            lambda h, w, c, y, wt: chunked_cross_entropy(
                h, w, c, y, 16, "test", 0.5, wt))(h, w, weight)
        want, want_grads = over_steps(
            lambda h, w, c, y, wt: plain(h, w, c, y, 0.5, wt))(h, w, weight)
    assert abs(float(loss) - float(want)) <= 1e-5 * abs(float(want))
    for got, wanted in zip(grads, want_grads):
        close(got, wanted)


def test_compute_dtype_operands_float32_accumulator():
    """bf16 states against a float32 head: the head is cast to the states'
    dtype, the gradient back to the states has their dtype, and the head's
    gradient is float32 in the head's own shape."""
    h, w, contract, targets, _ = inputs("VD", False)
    grads = jax.grad(
        lambda h, w: chunked_cross_entropy(
            h, w, contract, targets, 16, "test")[0], (0, 1))(
                h.astype(jnp.bfloat16), w)
    assert (grads[0].dtype, grads[0].shape) == (jnp.bfloat16, h.shape)
    assert (grads[1].dtype, grads[1].shape) == (jnp.float32, w.shape)
    want = jax.grad(lambda h, w: plain(h, w, contract, targets, 1.0, None)[0],
                    (0, 1))(h, w)
    for got, wanted in zip(grads, want):
        assert float(jnp.linalg.norm(got.astype(jnp.float32) - wanted)
                     / jnp.linalg.norm(wanted)) <= 2e-2


# -- the logits are computed once ------------------------------------------------


def vocabulary_products(jaxpr, vocab):
    """{path: count} of the ``dot_general`` equations with an axis of
    ``vocab`` among their operands' or result's dimensions, by the ``scan``
    equations they lie under (``tests/test_looplm._pallas_calls``'s walk)."""
    found = {}

    def walk(jaxpr, path):
        scans = 0
        for eqn in jaxpr.eqns:
            here = path
            if eqn.primitive.name == "scan":
                here = path + (f"scan{scans}",)
                scans += 1
            shapes = [v.aval.shape for v in eqn.invars + eqn.outvars]
            if eqn.primitive.name == "dot_general" and any(
                    vocab in shape for shape in shapes):
                found[path] = found.get(path, 0) + 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub, here)

    walk(jaxpr, ())
    return found


def tiny(kind):
    vocab = 200  # no other dimension of either model
    if kind == "looplm":
        return vocab, LoopLM(
            vocab_size=vocab, hidden_size=32, num_heads=2, num_layers=1,
            intermediate_size=48, loop_steps=2, dtype=jnp.float32,
            loss_chunk=16)
    return vocab, HybridLM(
        vocab_size=vocab, layer_types=("mamba", "attention"), hidden_size=32,
        num_heads=2, num_kv_heads=1, intermediate_size=48, mamba_heads=4,
        mamba_head_dim=16, mamba_state=8, mamba_chunk=8, dtype=jnp.float32,
        loss_chunk=16)


@pytest.mark.parametrize("remat", [True, False], ids=["remat", "no_remat"])
@pytest.mark.parametrize("kind", ["looplm", "hybridlm"])
def test_a_gradient_computes_each_chunks_logits_once(kind, remat):
    """Three products over the vocabulary in the loss's chunk loop under a
    gradient (logits, the gradient back to the state, the head's gradient),
    all in ONE loop, the forward sweep's; one without a gradient. A
    recomputed ``chunk_ce`` had four, in two loops."""
    vocab, module = tiny(kind)
    module = module.clone(remat=remat)
    x = jnp.zeros((2, 33), jnp.int32)
    params = jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), x, None, method="loss"))

    def loss(p):
        return module.apply(p, x, method="loss")[0]

    forward = vocabulary_products(jax.make_jaxpr(loss)(params).jaxpr, vocab)
    assert list(forward.values()) == [1], forward
    gradient = vocabulary_products(
        jax.make_jaxpr(jax.grad(loss))(params).jaxpr, vocab)
    assert list(gradient.values()) == [3], gradient
    (path,) = gradient
    assert path and path[-1].startswith("scan")
