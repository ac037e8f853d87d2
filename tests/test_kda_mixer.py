"""``ops.kda_mixer``: the KDA mixer's element-wise chain around its scan as
fused kernels (run here through the Pallas interpreter) against ``HybridLM.
_kda``'s plain ``jnp`` chain, the SAME method with the kernels refused: the
mixer's result and every gradient (the layer's input through the five
products ``W_q``, ``W_k``, ``W_v``, ``W_f`` and ``W_g``; ``conv_w``, ``A_log``,
``dt_bias``, ``gate_norm``), float32 / highest and bf16 as run, log-decays
from none to the floor, over two token tiles of eight turns (the
convolution reads across the tiles' edge and its gradient waits there); the
mesh; what ``fit_facts`` says; and that five layers trace a kernel's body
once."""

import jax
import jax.numpy as jnp
import pytest

import ling_hybrid_model as lm
from ling_hybrid_model import no_persistent_cache  # noqa: F401 - autouse
from raydp_tpu.ops import delta_rule, kda_mixer

B, T = 2, 1024


def stand_in(q, k, v, log_alpha, beta):
    """In the scan's place: cheap, and every operand reaches the result and
    gets a gradient of its own size (the scan itself against its recurrence
    is ``test_channel_delta_rule.py``'s). Operands and result [b, t, h, d]
    or flat, [b, t, h x d], as the scan takes and gives them."""
    f32, gives, dtype = jnp.float32, v.shape, q.dtype
    q, k, v, log_alpha = (x.astype(f32).reshape(beta.shape + (-1,))
                          for x in (q, k, v, log_alpha))
    o = (v * jnp.tanh(4.0 * jnp.sum(q * k, axis=-1, keepdims=True))
         + 3.0 * q * jnp.exp(0.2 * log_alpha) + k) * beta[..., None]
    return o.astype(dtype).reshape(gives)


@pytest.fixture(scope="module")
def layer():
    m = lm.model()
    w = lm.params(m, lm.batch())["params"]["layer_0"]
    a = jax.random.normal(jax.random.PRNGKey(5), (B, T, m.hidden_size))
    weight = jax.random.normal(jax.random.PRNGKey(6), (B, T, m.hidden_size))
    return w, a, weight


def mixer(m, w, a, weight, fused, monkeypatch, scan=stand_in):
    """(``_kda``'s result, its gradients by name) with the chain's kernels
    (``fused``) or with them refused: the plain chain."""
    with monkeypatch.context() as patch:
        patch.setattr(delta_rule, "channel_gated_delta_rule", scan)
        if not fused:
            patch.setattr(kda_mixer, "refused", lambda *sizes: "the test's")
        assert m._kda_mixer(a.shape[1])[0] == ("kernel" if fused else "xla")

        def loss(w, a):
            out = m._kda(w, a.astype(m.dtype))
            return (out.astype(jnp.float32) * weight).sum(), out

        (_, out), (dw, da) = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True))(w, a)
    names = ("conv_w", "A_log", "dt_bias", "gate_norm", "wq", "wk", "wv",
             "wf", "wg", "wb", "wo")
    return out, {"a": da, **{name: dw[name] for name in names}}


def gap(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want)
                 / jnp.maximum(jnp.linalg.norm(want), 1e-12))


# where exp(A_log) (pf + dt_bias) lies: the gate's sigmoid at 0 (no decay),
# in its live range, at 1 (every channel at ``kda_decay_floor``)
DECAYS = {"none": -40.0, "mixed": 0.0, "floor": 40.0}


@pytest.mark.parametrize("decay", list(DECAYS))
@pytest.mark.parametrize("dtype, precision, value, gradient", [
    (jnp.float32, "highest", 2e-6, 2e-5),
    # as run: both chains round the same float32 numbers to bf16, and where
    # a sum over a head lands on the other side of a rounding the results
    # differ by one place of eight bits
    (jnp.bfloat16, None, 4e-3, 2e-2)])
def test_the_fused_chain_is_the_plain_chain(layer, monkeypatch, dtype,
                                            precision, value, gradient, decay):
    w, a, weight = layer
    w = {**w, "dt_bias": w["dt_bias"] + DECAYS[decay]}
    m = lm.model(dtype=dtype)
    with jax.default_matmul_precision(precision):
        got, got_g = mixer(m, w, a, weight, True, monkeypatch)
        want, want_g = mixer(m, w, a, weight, False, monkeypatch)
    assert got.dtype == want.dtype == dtype and got.shape == want.shape
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())
    assert gap(got, want) < value, gap(got, want)
    for name in want_g:
        assert got_g[name].shape == want_g[name].shape, name
        assert bool(jnp.isfinite(got_g[name]).all()), name
        if float(jnp.abs(want_g[name]).max()) < 1e-20:  # a saturated gate
            assert float(jnp.abs(got_g[name]).max()) < 1e-20, name
            continue
        assert gap(got_g[name], want_g[name]) < gradient, (
            name, gap(got_g[name], want_g[name]))
    assert float(jnp.abs(want_g["conv_w"]).max()) > 0
    if decay == "mixed":
        assert all(float(jnp.abs(want_g[n]).max()) > 0
                   for n in ("A_log", "dt_bias", "wf"))


@pytest.mark.parametrize("decay, low, high", [
    ("none", -1e-6, 0.0), ("mixed", -5.0, 0.0), ("floor", -5.0, -5.0 + 1e-5)])
def test_the_log_decay_lies_between_the_floor_and_none(layer, decay, low, high):
    w, a, _ = layer
    m = lm.model()
    products = [m._dot(a, w[name]) for name in ("wq", "wk", "wv", "wf")]
    q, k, v, log_alpha = kda_mixer.operands(
        *products, w["conv_w"], w["A_log"], w["dt_bias"] + DECAYS[decay],
        m.kda_decay_floor)
    assert log_alpha.dtype == jnp.float32 and log_alpha.shape == q.shape
    assert low <= float(log_alpha.min()) <= float(log_alpha.max()) <= high
    # q and k leave l2-normed a head, q scaled by Dk^-0.5
    heads = m.delta_heads
    norms = jnp.linalg.norm(k.reshape(B, T, heads, -1), axis=-1)
    assert float(jnp.abs(norms - 1.0).max()) < 1e-3
    norms = jnp.linalg.norm(q.reshape(B, T, heads, -1), axis=-1)
    assert float(jnp.abs(norms - m.delta_key_dim ** -0.5).max()) < 1e-3


def test_under_a_mesh_each_device_runs_the_kernels_on_its_rows(
        layer, monkeypatch):
    """XLA cannot partition a Mosaic call: under a mesh that splits the
    batch the four calls go through ``shard_map``, and the gradients of what
    every device holds whole (the taps, the decay's vectors, the gain) are
    summed over the devices."""
    from raydp_tpu.parallel import make_mesh

    w, a, weight = layer
    a, weight = a[:, :64], weight[:, :64]
    m = lm.model()
    with jax.default_matmul_precision("highest"):
        want, want_g = mixer(m, w, a, weight, True, monkeypatch)
        with jax.set_mesh(make_mesh({"data": 2}, jax.devices()[:2])):
            got, got_g = mixer(m, w, a, weight, True, monkeypatch)
    assert gap(got, want) < 1e-6
    # a gradient not summed over the devices would be off by half; ``wb``'s
    # is a sum of cancelling terms here and reads 5e-5 between any two
    # programs (fused against plain on one device too)
    for name in want_g:
        assert gap(got_g[name], want_g[name]) < 2e-4, name


def test_the_real_scan_between_the_kernels(layer, monkeypatch):
    """The chain's results as the scan's calls read them and the scan's
    ``o`` as the read-out takes it: ``[T, H x d]`` on both sides, nothing
    between. (The whole model against its reference:
    ``test_ling_hybridlm.py``.)"""
    w, a, weight = layer
    a, weight = a[:, :128], weight[:, :128]
    m = lm.model()
    real = delta_rule.channel_gated_delta_rule
    with jax.default_matmul_precision("highest"):
        got, got_g = mixer(m, w, a, weight, True, monkeypatch, scan=real)
        want, want_g = mixer(m, w, a, weight, False, monkeypatch, scan=real)
    assert gap(got, want) < 1e-5
    for name in want_g:
        assert gap(got_g[name], want_g[name]) < 1e-4, name


def test_what_the_kernels_do_not_take_runs_as_plain_jnp(layer, monkeypatch):
    """A sequence that is not whole tiles of sixteen rows: ``refused`` says
    so, ``fit_facts`` repeats it, and the mixer runs its plain chain."""
    assert kda_mixer.refused(1024, 4, 16, 16) is None
    assert "40 tokens" in kda_mixer.refused(40, 4, 16, 16)
    assert "12 taps" in kda_mixer.refused(1024, 12, 16, 16)
    m = lm.model()
    facts = m.fit_facts(jnp.zeros((1, 41), jnp.int32))
    assert facts["delta.mixer"] == "xla"
    assert "40 tokens" in facts["delta.mixer_why_not"]
    assert "delta.mixer_fused_layers" not in facts
    w, a, weight = layer
    out, _ = mixer(m, w, a[:, :8], weight[:, :8], False, monkeypatch)
    assert out.shape == (B, 8, m.hidden_size)


def test_fit_facts_say_what_runs_the_mixer_and_what_is_kept():
    m = lm.model(attn_impl="flash", dtype=jnp.bfloat16)
    facts = m.fit_facts(lm.batch())
    assert facts["delta.mixer"] == "kernel"
    assert facts["delta.mixer_fused_layers"] == 5
    assert "delta.mixer_why_not" not in facts
    keeps = facts["remat_keeps"].split(",")
    assert {"delta_out", kda_mixer.OPERANDS, kda_mixer.READ_OUT} <= set(keeps)
    # q, k, v in bf16 and the log-decay in float32, W_o's input in bf16: a
    # row of 32 tokens x 2 heads of 16, five layers
    wide = lm.T * 2 * 16
    assert facts["remat_kept_bytes_per_row"] >= 5 * wide * (3 * 2 + 4 + 2 + 2)


def test_five_layers_trace_a_kernels_body_once(monkeypatch):
    """Each call's entry is one ``jax.jit`` of its module: the five KDA
    layers of the cell's stage (and a recomputed block's second pass over
    them) share ONE trace of a kernel's body and one lowered function, the
    scan's two calls among them."""
    counts = {}

    def counted(module, name):
        body = getattr(module, name)

        def wrapper(*args, **kwargs):
            counts[name] = counts.get(name, 0) + 1
            return body(*args, **kwargs)

        monkeypatch.setattr(module, name, wrapper)

    for name in ("_operands_kernel", "_operands_grad_kernel",
                 "_read_out_kernel", "_read_out_grad_kernel"):
        counted(kda_mixer, name)
    for name in ("_forward_kernel", "_backward_kernel"):
        counted(delta_rule, name)
    jax.clear_caches()
    m = lm.model(attn_impl="flash", remat=True)
    assert m.layer_types.count("kda") == 5
    x = jax.random.randint(jax.random.PRNGKey(1), (1, 65), 0, lm.V)
    p = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0), x, None,
                                      method="loss"))
    # two programs as a fit compiles them, each under ``jax.jit`` (the trace
    # above was under none: another context, a cache entry of its own): the
    # training step, with its recomputed blocks, and the evaluation
    counts.clear()
    text = jax.jit(jax.grad(lambda q: m.apply(
        q, x, None, method="loss"), has_aux=True)).lower(p).as_text()
    jax.jit(lambda q: m.apply(q, x, None, method="loss")).lower(p)
    assert counts == {name: 1 for name in counts} and len(counts) == 6, counts
    # the lowered module holds each kernel's function once, called five times
    assert text.count("func.func private @_operands_call") == 1


def test_the_grid_step_does_not_show_in_the_result(monkeypatch):
    """Four heads a grid step in turns of 64 rows (the Ling cell's: 32 heads)
    against one head in turns of 16: values and gradients of both pairs of
    calls, bit for bit but for the sums XLA takes over the grid steps'
    rows."""
    h, d, t = 4, 16, 1024
    keys = jax.random.split(jax.random.PRNGKey(11), 10)
    products = [jax.random.normal(k, (1, t, h * d)) for k in keys[:4]]
    conv_w = jax.random.uniform(keys[4], (4, 3 * h * d), minval=-0.5,
                                maxval=0.5)
    a_log, dt_bias = (0.3 * jax.random.normal(keys[5], (h,)),
                      jax.random.normal(keys[6], (h * d,)))
    gate = jax.nn.sigmoid(jax.random.normal(keys[7], (1, t, h)))
    gain = 1.0 + 0.1 * jax.random.normal(keys[8], (d,))
    weight = jax.random.normal(keys[9], (5, 1, t, h * d))

    def loss(products, conv_w, a_log, dt_bias, gate, gain):
        q, k, v, log_alpha = kda_mixer.operands(
            *products, conv_w, a_log, dt_bias, -5.0)
        y = kda_mixer.read_out(v, gate, gain, 1e-6)
        return sum((x * w).sum()
                   for x, w in zip((q, k, v, log_alpha, y), weight))

    def run():
        jax.clear_caches()
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5))(
            products, conv_w, a_log, dt_bias, gate, gain)

    assert kda_mixer._tiles(t, h) == (512, 4, 64)
    want = run()
    monkeypatch.setattr(kda_mixer, "ROWS", 16)
    monkeypatch.setattr(kda_mixer, "HEADS_A_STEP", 1)
    assert kda_mixer._tiles(t, h) == (512, 1, 16)
    got = run()
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert gap(a, b) < 1e-6
