"""``ops/flash_attention`` alone, in interpret mode on the CPU: the kernels
against their references forward and backward, the one-call backward against
the two-call pass bit for bit and where each form is taken, the tiles the
backward picks, and the window: the kernels against ``full_attention`` under
the same mask, the whole-sequence window as the causal call, the grids that
follow the window. (Compiled for a described chip: ``test_tpu_compile_*``;
inside the models: the models' own files.)"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raydp_tpu.parallel.ring_attention import full_attention


def test_flash_attention_matches_reference():
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops import flash_attention
    from raydp_tpu.ops.flash_attention import _reference

    rng = np.random.default_rng(7)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 4, 128, 32)), jnp.float32)
        for _ in range(3)
    )
    for causal in (False, True):
        out = flash_attention(q, k, v, causal, 64, 64)
        ref = _reference(q, k, v, causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    # gradients flow through the custom VJP
    grad = jax.grad(lambda q_: jnp.sum(flash_attention(q_, k, v, True, 64, 64) ** 2))(q)
    ref_grad = jax.grad(lambda q_: jnp.sum(_reference(q_, k, v, True) ** 2))(q)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad), atol=5e-4)


def test_flash_attention_backward_blockwise_exact():
    """The pallas backward (dq/dk/dv from saved o + logsumexp — no [T,T]
    matrix) must match gradients through the exact reference for every input,
    both maskings, and blocks that straddle the causal diagonal."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops import flash_attention
    from raydp_tpu.ops.flash_attention import _reference

    rng = np.random.default_rng(13)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 3, 256, 32)), jnp.float32)
        for _ in range(3)
    )
    g = jnp.asarray(rng.standard_normal((2, 3, 256, 32)), jnp.float32)

    for causal in (False, True):
        for bq, bk in ((64, 64), (128, 32)):
            _, vjp = jax.vjp(
                lambda q_, k_, v_: flash_attention(q_, k_, v_, causal, bq, bk),
                q, k, v,
            )
            dq, dk, dv = vjp(g)
            _, ref_vjp = jax.vjp(
                lambda q_, k_, v_: _reference(q_, k_, v_, causal), q, k, v
            )
            rdq, rdk, rdv = ref_vjp(g)
            np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq), atol=1e-4)
            np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk), atol=1e-4)
            np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv), atol=1e-4)


def _flash_module():
    # ``raydp_tpu.ops.flash_attention`` the attribute is the function
    import importlib

    return importlib.import_module("raydp_tpu.ops.flash_attention")


def _flash_grads(fa, q, k, v, g, causal=True, block_q=None, block_k=None,
                 window=None):
    import jax

    _, vjp = jax.vjp(
        lambda q_, k_, v_: fa.flash_attention(
            q_, k_, v_, causal, block_q, block_k, None, window), q, k, v)
    return vjp(g)


def _backward_calls(fa, q, causal=True, block_q=None, block_k=None,
                    window=None):
    """The names of the Mosaic calls in the gradient's jaxpr."""
    import re

    import jax

    text = str(jax.make_jaxpr(lambda q_, k_, v_, g_: _flash_grads(
        fa, q_, k_, v_, g_, causal, block_q, block_k, window))(q, q, q, q))
    return sorted(set(re.findall(r"flash_attention_(?:window_)?bwd_\w+", text)))


# window: None = causal; in blocks of 16 rows: 1 key, a block, several
# blocks (2.5), the whole sequence; T of 2, 4 and 8 blocks
FUSED_CASES = [
    (None, 4, 32, "float32"), (1, 4, 32, "float32"), (16, 4, 32, "float32"),
    (40, 4, 32, "float32"), (64, 4, 32, "float32"),
    (None, 2, 32, "float32"), (40, 2, 32, "float32"),
    (None, 8, 32, "float32"), (40, 8, 32, "float32"),
    (None, 4, 64, "float32"), (40, 4, 64, "float32"),
    (None, 4, 128, "float32"), (40, 4, 128, "float32"),
    (None, 4, 32, "bfloat16"), (1, 4, 32, "bfloat16"),
    (40, 4, 64, "bfloat16"), (16, 8, 128, "bfloat16"),
    (None, 2, 128, "bfloat16"),
]


@pytest.mark.parametrize("window, blocks, head, dtype", FUSED_CASES)
def test_flash_backward_fused_equals_two_call(monkeypatch, window, blocks,
                                              head, dtype):
    """The ONE-call backward pass (every live tile's scores, probabilities
    and ``ds`` computed once, dq a head long in VMEM) gives the two-call
    pass's dq, dk and dv BIT FOR BIT, causal and under every kind of window,
    and (float32) the exact reference's gradients at the blockwise test's
    tolerance."""
    import jax.numpy as jnp

    from raydp_tpu.parallel import full_attention

    fa = _flash_module()
    block, t = 16, 16 * blocks
    rng = np.random.default_rng(43)
    q, k, v, g = (jnp.asarray(rng.standard_normal((2, 2, t, head)), dtype)
                  for _ in range(4))
    assert fa.backward_form(t, t, head, q.dtype.itemsize, block_q=block,
                            block_k=block) == "fused"
    calls = _backward_calls(fa, q, True, block, block, window)
    hidden = window is not None and window < t
    assert calls == [("flash_attention_window_bwd_dq_dkv" if hidden
                      else "flash_attention_bwd_dq_dkv")]
    fused = _flash_grads(fa, q, k, v, g, True, block, block, window)
    monkeypatch.setattr(fa, "backward_form", lambda *a, **kw: "two_call")
    assert len(_backward_calls(fa, q, True, block, block, window)) == 2
    two_call = _flash_grads(fa, q, k, v, g, True, block, block, window)
    for name, got, want in zip(("dq", "dk", "dv"), fused, two_call):
        assert got.dtype == want.dtype == q.dtype, name
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)), err_msg=name)
    if dtype == "float32":
        import jax

        _, ref_vjp = jax.vjp(lambda q_, k_, v_: full_attention(
            q_, k_, v_, causal=True, window=window), q, k, v)
        for name, got, want in zip(("dq", "dk", "dv"), fused, ref_vjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=1e-4, err_msg=name)


@pytest.mark.parametrize("case", [
    "runtime_offsets", "tq_is_not_tk", "non_causal", "unequal_blocks",
    "dq_past_the_vmem_bound"])
def test_backward_form_keeps_the_two_call_pass(monkeypatch, case):
    """What the fused form does not cover runs the two-call pass, decided
    from the shapes and arguments alone, and gives the gradients it gave."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel import full_attention

    fa = _flash_module()
    rng = np.random.default_rng(44)
    t, d, block = 64, 32, 16

    def randn(rows):
        return jnp.asarray(rng.standard_normal((1, 2, rows, d)), jnp.float32)

    q, k, v, g = randn(t), randn(t), randn(t), randn(t)
    assert fa.backward_form(t, t, d, 4, block_q=block, block_k=block) == "fused"
    two_names = ["flash_attention_bwd_dkv", "flash_attention_bwd_dq"]

    def reference(causal, k_=k, v_=v):
        _, vjp = jax.vjp(lambda a, b, c: full_attention(a, b, c, causal=causal),
                         q, k_, v_)
        return vjp(g)

    if case == "runtime_offsets":
        # a ring step's call: the offsets are values of the program
        assert fa.backward_form(
            t, t, d, 4, block_q=block, block_k=block,
            q_offset=jnp.int32(0), k_offset=0) == "two_call"
        o = full_attention(q, k, v, causal=True)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * d ** -0.5
        s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
        lse = jax.nn.logsumexp(s, axis=-1)
        dsum = jnp.sum(g * o, axis=-1)

        def ring_step(q_off, k_off):
            return fa.flash_backward_blocks(
                q, k, v, lse, dsum, g, q_off, k_off, True, block, block)

        text = str(jax.make_jaxpr(ring_step)(jnp.int32(0), jnp.int32(0)))
        assert "bwd_dq_dkv" not in text and "flash_attention_bwd_dkv" in text
        got = jax.jit(ring_step)(jnp.int32(0), jnp.int32(0))
        want = reference(True)
        # the fused call of the same tiles (static offsets): the same bits
        for a, b in zip(got, fa.flash_backward_blocks(
                q, k, v, lse, dsum, g, 0, 0, True, block, block)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    elif case == "tq_is_not_tk":
        k, v = randn(2 * t), randn(2 * t)
        assert fa.backward_form(t, 2 * t, d, 4, block_q=block,
                                block_k=block) == "two_call"
        _, vjp = jax.vjp(lambda a, b, c: fa.flash_attention(
            a, b, c, True, block, block), q, k, v)
        got, want = vjp(g), reference(True, k, v)
    elif case == "non_causal":
        assert fa.backward_form(t, t, d, 4, causal=False, block_q=block,
                                block_k=block) == "two_call"
        assert _backward_calls(fa, q, False, block, block) == two_names
        got = _flash_grads(fa, q, k, v, g, False, block, block)
        want = reference(False)
    elif case == "unequal_blocks":
        assert fa.backward_form(t, t, d, 4, block_q=32,
                                block_k=block) == "two_call"
        assert _backward_calls(fa, q, True, 32, block) == two_names
        got = _flash_grads(fa, q, k, v, g, True, 32, block)
        want = reference(True)
    else:
        # from the shapes: 128k rows of 128 are a dq of 64 MB, past what a
        # call may ask for beside its tiles; half of that is not
        assert fa.backward_form(131072, 131072, 128) == "two_call"
        assert fa.backward_form(65536, 65536, 128) == "fused"
        assert fa.dq_resident_bytes(16384, 128) == 8 * 2**20
        assert fa.dq_resident_bytes(8192, 64) == 4 * 2**20  # lane-padded
        assert fa.fused_vmem_bytes(16384, 128, 1024, 2) > (
            fa.VMEM_DEFAULT_BYTES + fa.dq_resident_bytes(16384, 128))
        # the same decision at a size the interpreter runs
        monkeypatch.setattr(fa, "VMEM_ASK_BOUND_BYTES",
                            fa.fused_vmem_bytes(t, d, block, 4) - 1)
        assert fa.backward_form(t, t, d, 4, block_q=block,
                                block_k=block) == "two_call"
        assert _backward_calls(fa, q, True, block, block) == two_names
        got = _flash_grads(fa, q, k, v, g, True, block, block)
        want = reference(True)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4,
                                   err_msg=f"{case} {name}")


def test_flash_backward_blocks_picks_its_tiles_as_the_forward_does(monkeypatch):
    """Past a head of 128 the forward's tile halves (``pick_blocks`` keeps
    a tile's VMEM footprint): the backward pass asks with the same head."""
    import jax
    import jax.numpy as jnp

    fa = _flash_module()
    asked = []
    pick = fa.pick_blocks

    def noting(*args, **kwargs):
        asked.append(kwargs.get("head_dim"))
        return pick(*args, **kwargs)

    monkeypatch.setattr(fa, "pick_blocks", noting)
    q = jax.ShapeDtypeStruct((1, 1, 1024, 256), jnp.bfloat16)
    jax.eval_shape(lambda q_, k_, v_, g_: _flash_grads(fa, q_, k_, v_, g_),
                   q, q, q, q)
    assert asked and set(asked) == {256}
    assert pick(1024, 1024, head_dim=256) == (512, 512)


def test_flash_attention_training_memory_is_linear():
    """Jaxpr-level check that the backward never materializes a [T, T]
    score matrix: the largest intermediate in the VJP scales with T, not T²
    (the round-1 backward recomputed through full attention and OOMed at
    the lengths the forward could handle)."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops import flash_attention

    t = 2048
    q = jax.ShapeDtypeStruct((1, 1, t, 32), jnp.float32)

    def loss(q_, k_, v_):
        return jnp.sum(flash_attention(q_, k_, v_, True, 128, 128) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q)

    def subjaxprs(eqn):
        for val in eqn.params.values():
            for v in val if isinstance(val, (list, tuple)) else [val]:
                if hasattr(v, "jaxpr"):
                    yield v.jaxpr
                elif hasattr(v, "eqns"):
                    yield v

    def max_elems(jpr):
        worst = 0
        for eqn in jpr.eqns:
            for var in eqn.outvars:
                shape = getattr(var.aval, "shape", ())
                n = int(np.prod(shape)) if shape else 1
                worst = max(worst, n)
            for sub in subjaxprs(eqn):
                worst = max(worst, max_elems(sub))
        return worst

    largest = max_elems(jaxpr.jaxpr)
    # O(T): q itself is t*32 elems; a [T,T] matrix would be t*t = 64x larger
    assert largest <= t * 32 * 4, (
        f"backward materializes an intermediate of {largest} elements "
        f"(≥ [T,T] = {t*t})"
    )


def test_flash_attention_composes_with_shard_map(cpu_mesh_devices):
    """Mosaic kernels can't be AUTO-partitioned, but under shard_map (manual
    partitioning) the flash kernel runs per shard — the composition ring
    attention's per-device block math will use."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.ops import flash_attention
    from raydp_tpu.ops.flash_attention import _reference
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 4}, jax.devices()[:4])
    rng = np.random.default_rng(13)
    q, k, v = (
        jnp.asarray(rng.standard_normal((8, 2, 64, 16)), jnp.float32)
        for _ in range(3)
    )
    spec = P("data", None, None, None)  # batch-sharded; attention is local
    # check_vma=False: the pallas interpreter can't reconcile invariant grid
    # slices with varying operands (JAX's documented workaround)
    out = jax.shard_map(
        lambda q_, k_, v_: flash_attention(q_, k_, v_, True, 32, 32),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )(q, k, v)
    ref = _reference(q, k, v, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


# -- the window in the flash kernels ----------------------------------------------

fa = _flash_module()



def _qkv(t, heads=2, group=1, d=32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed + t), 4)
    q = jax.random.normal(keys[0], (1, heads * group, t, d))
    k, v = (jnp.repeat(jax.random.normal(key, (1, heads, t, d)), group, axis=1)
            for key in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], q.shape)


def _value_and_grads(attend, q, k, v, g):
    return jax.value_and_grad(
        lambda q, k, v: jnp.sum(attend(q, k, v) * g), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("t, window, block_q, block_k, group", [
    (320, 100, 64, 64, 1),   # T no multiple of W
    (256, 16, 64, 64, 1),    # W smaller than a block
    (256, 64, 64, 32, 1),    # q tiles wider than k tiles
    (256, 96, 32, 64, 1),    # and narrower
    (256, 130, 128, 128, 7),  # heads 7 to 1, W just past a block
    (256, 255, 64, 64, 1),   # one key hidden
])
def test_the_window_kernels_are_full_attention_under_the_same_mask(
        t, window, block_q, block_k, group):
    q, k, v, g = _qkv(t, heads=1 if group > 1 else 2, group=group)
    got, g_got = _value_and_grads(
        lambda q, k, v: fa.flash_attention(
            q, k, v, True, block_q, block_k, None, window), q, k, v, g)
    want, g_want = _value_and_grads(
        lambda q, k, v: full_attention(q, k, v, True, window), q, k, v, g)
    assert abs(float(got - want)) <= 1e-4 * max(1.0, abs(float(want)))
    for name, a, b in zip("qkv", g_got, g_want):
        assert float(jnp.abs(a - b).max()) <= 2e-5, name
    # and the window hides something: the causal call differs
    causal = fa.flash_attention(q, k, v, True, block_q, block_k)
    windowed = fa.flash_attention(q, k, v, True, block_q, block_k, None, window)
    assert float(jnp.abs(causal - windowed).max()) > 1e-3


@pytest.mark.parametrize("window", [256, 300])
def test_a_window_of_the_whole_sequence_is_the_causal_call_bit_for_bit(window):
    q, k, v, g = _qkv(256)
    want, g_want = _value_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, True, 64, 64), q, k, v, g)
    got, g_got = _value_and_grads(
        lambda q, k, v: fa.flash_attention(q, k, v, True, 64, 64, None, window),
        q, k, v, g)
    assert float(got) == float(want)
    for a, b in zip(g_got, g_want):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    text = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, True, 64, 64, None, window)).lower(q, k, v).as_text()
    assert "flash_attention_window" not in text


def test_without_a_window_the_kernels_lower_to_the_program_they_were():
    q, k, v, g = _qkv(256)

    def text(*window):
        return jax.jit(lambda q, k, v: _value_and_grads(
            lambda q, k, v: fa.flash_attention(q, k, v, True, 64, 64, *window),
            q, k, v, g)).lower(q, k, v).as_text()

    assert text() == text(None, None)
    assert text(None, 100) != text()


@pytest.mark.parametrize("t, block_q, block_k, window", [
    (16384, 1024, 1024, 4096), (16384, 512, 512, 4096), (256, 64, 32, 64),
    (256, 32, 64, 96), (320, 64, 64, 100), (256, 64, 64, 1)])
def test_the_grid_follows_the_window(t, block_q, block_k, window):
    """The inner axis has as many steps as the blocks a window can touch
    (counted here key by key), never more than the bound from the spans,
    and the forward call's grid says so."""
    k_steps, q_steps = fa.window_steps(t, block_q, block_k, window)
    rows = np.arange(t)
    seen = (rows[:, None] >= rows[None, :]) & (
        rows[:, None] - rows[None, :] < window)
    blocks = seen.reshape(t // block_q, block_q, t // block_k, block_k).any(
        axis=(1, 3))
    assert k_steps == blocks.sum(axis=1).max()
    assert q_steps == blocks.sum(axis=0).max()
    assert k_steps <= math.ceil((window + block_q - 1) / block_k) + 1
    # the first live block is where the index maps start
    for i in range(t // block_q):
        assert int(fa._first_k_block(i, block_q, block_k, window)) == int(
            np.argmax(blocks[i]))
    for j in range(t // block_k):
        assert fa._first_q_block(j, block_q, block_k) == int(
            np.argmax(blocks[:, j]))
    if t <= 320:
        q = jnp.zeros((1, 1, t, 32))
        jaxpr = str(jax.make_jaxpr(lambda q: fa.flash_attention(
            q, q, q, True, block_q, block_k, None, window))(q))
        assert f"grid=(1, {t // block_q}, {k_steps})" in jaxpr.replace(
            "\n", ""), jaxpr[:2000]


def test_a_window_is_refused_where_the_kernels_do_not_build_it():
    q, k, v, _ = _qkv(128)
    with pytest.raises(ValueError, match="causal self-attention"):
        fa.flash_attention(q, k, v, False, 64, 64, None, 32)
    with pytest.raises(ValueError, match="causal self-attention"):
        fa.flash_attention(q[:, :, :64], k, v, True, 64, 64, None, 32)
    with pytest.raises(ValueError, match="causal"):
        full_attention(q, k, v, False, 32)
    # a ring's step hands the backward blocks their offsets: the window's
    # grids count from position 0 and would skip live blocks
    stats = jnp.zeros(q.shape[:3], jnp.float32)
    for offsets in ((64, 0), (0, 64), (jnp.int32(0), 0)):
        with pytest.raises(ValueError, match="static 0"):
            fa.flash_backward_blocks(q, k, v, stats, stats, q, *offsets,
                                     True, 64, 64, None, 32)
    from raydp_tpu.models.transformer import _attend

    with pytest.raises(ValueError, match="builds no window"):
        _attend(q, k, v, impl="ring", axis="sp", causal=True, window=32)


# -- ISSUE 54: the causal calls step over the tiles under the diagonal --------


# a cover of: T of 1, 2, 4, 8, 16 blocks x equal / wider q / wider k tiles x
# the head x the dtype (every pair of them in one of the three files' cases;
# the whole product is the interpreter's minutes)
@pytest.mark.parametrize("d, blocks, block_q, block_k, dtype", [
    (64, 1, 16, 16, jnp.float32), (64, 2, 32, 16, jnp.bfloat16),
    (64, 4, 16, 32, jnp.float32), (64, 8, 16, 16, jnp.bfloat16),
    (64, 16, 16, 16, jnp.float32), (64, 16, 32, 16, jnp.bfloat16),
    (128, 1, 16, 32, jnp.bfloat16), (128, 2, 16, 16, jnp.float32),
    (128, 4, 32, 16, jnp.float32), (128, 8, 16, 32, jnp.bfloat16),
    (128, 16, 16, 16, jnp.bfloat16)])
def test_the_live_grid_gives_the_rectangular_grids_bits(
        d, blocks, block_q, block_k, dtype):
    """Heads of 64 and 128 (256: ``test_flash_head_256.py``; 192 over 128:
    ``test_flash_value_width.py``)."""
    from flash_grid_cases import live_grid_against_rectangular

    live_grid_against_rectangular(blocks, block_q, block_k, d, d, dtype)


@pytest.mark.parametrize("t, block_q, block_k, want", [
    (16 * 8, 8, 8, (256, 136)), (8 * 16, 16, 16, (64, 36)),
    (4 * 16, 16, 16, (16, 10)), (64, 64, 64, (1, 1)),
    (256, 64, 32, (32, 20)), (256, 32, 64, (32, 20)), (384, 128, 32, (36, 24)),
    (32 * 8, 8, 8, (1024, 528))])
def test_causal_steps_against_a_brute_force_count(t, block_q, block_k, want):
    """A tile is live where any of its queries sees any of its keys: counted
    from the whole mask, tile by tile; the tables list exactly those tiles,
    an outer block's inner ones ascending, in both orders."""
    fa = _flash_module()
    mask = np.tril(np.ones((t, t), bool)).reshape(
        t // block_q, block_q, t // block_k, block_k).any(axis=(1, 3))
    assert fa.causal_steps(t, block_q, block_k) == (mask.size, mask.sum()) == want
    by_q = fa._live_tiles(t, block_q, block_k, "q")
    by_k = fa._live_tiles(t, block_q, block_k, "k")
    assert by_q.dtype == by_k.dtype == np.int32
    assert by_q.T.tolist() == [list(ij) for ij in zip(*np.nonzero(mask))]
    assert by_k.T.tolist() == [list(ji) for ji in zip(*np.nonzero(mask.T))]
    for j in range(t // block_k):
        assert by_k[1][by_k[0] == j][0] == fa._first_q_block(
            j, block_q, block_k)


@pytest.mark.parametrize("case", [
    "traced_offset", "tq_is_not_tk", "window", "non_causal",
    "tables_past_smem"])
def test_what_is_not_causal_from_zero_keeps_its_grid(monkeypatch, case):
    """The live grid is taken from what the call sees (causal, no window,
    both offsets the static 0, Tq = Tk) and by no flag; everything else
    steps over the grid it had."""
    from flash_grid_cases import pallas_grids

    fa = _flash_module()
    t, block = 128, 32
    q = jnp.zeros((1, 2, t, 32))
    stat = jnp.zeros((1, 2, t))
    rect = (2, 4, 4)
    if case == "traced_offset":
        assert fa.causal_grid(t, t, block, block, k_offset=jnp.int32(0)) == (
            "rectangular:runtime offsets")
        assert fa.causal_grid(t, t, block, block, q_offset=32).startswith(
            "rectangular")
        got = pallas_grids(lambda off: fa.flash_attention_stats(
            q, q, q, off, 0, True, block, block), jnp.int32(0))
        got += pallas_grids(lambda off: fa.flash_backward_blocks(
            q, q, q, stat, stat, q, 0, off, True, block, block), jnp.int32(0))
        want = [("flash_attention_fwd", rect), ("flash_attention_bwd_dq", rect),
                ("flash_attention_bwd_dkv", rect)]
    elif case == "tq_is_not_tk":
        k = jnp.zeros((1, 2, 2 * t, 32))
        assert fa.causal_grid(t, 2 * t, block, block).startswith("rectangular")
        got = pallas_grids(jax.grad(lambda q: fa.flash_attention(
            q, k, k, True, block, block).sum()), q)
        want = [("flash_attention_fwd", (2, 4, 8)),
                ("flash_attention_bwd_dq", (2, 4, 8)),
                ("flash_attention_bwd_dkv", (2, 8, 4))]
    elif case == "window":
        assert fa.causal_grid(t, t, block, block, window=40) == "window"
        # a window of the whole row or more IS the causal call
        assert fa.causal_grid(t, t, block, block, window=t) == "live"
        steps = fa.window_steps(t, block, block, 40)
        got = pallas_grids(jax.grad(lambda q: fa.flash_attention(
            q, q, q, True, block, block, None, 40).sum()), q)
        want = [("flash_attention_window_fwd", (2, 4, steps[0])),
                ("flash_attention_window_bwd_dq_dkv", (2, 4, steps[1]))]
    elif case == "non_causal":
        assert fa.causal_grid(t, t, block, block, causal=False) == (
            "rectangular:not causal")
        got = pallas_grids(jax.grad(lambda q: fa.flash_attention(
            q, q, q, False, block, block).sum()), q)
        want = [("flash_attention_fwd", rect), ("flash_attention_bwd_dq", rect),
                ("flash_attention_bwd_dkv", rect)]
    else:
        # two int32 a step in SMEM: past the tables' bound the rectangle stays
        assert fa.causal_grid(362 * 1024, 362 * 1024, 1024, 1024).startswith(
            "rectangular:more live tiles")
        assert fa.causal_grid(361 * 1024, 361 * 1024, 1024, 1024) == "live"
        monkeypatch.setattr(fa, "LIVE_GRID_MAX_STEPS", 9)
        got = pallas_grids(jax.grad(lambda q: fa.flash_attention(
            q, q, q, True, block, block).sum()), q)
        want = [("flash_attention_fwd", rect),
                ("flash_attention_bwd_dq_dkv", rect)]
    assert got == want
    live = pallas_grids(jax.grad(lambda q: fa.flash_attention(
        q, q, q, True, block, block).sum()), q)
    if case != "tables_past_smem":
        assert live == [("flash_attention_fwd", (2, 10)),
                        ("flash_attention_bwd_dq_dkv", (2, 10))]
