"""Models / ops / parallel tests on the virtual 8-device CPU mesh."""

from functools import partial

import numpy as np
import pytest


@pytest.fixture(scope="module")
def mesh8(cpu_mesh_devices):
    import jax
    from raydp_tpu.parallel import make_mesh

    return make_mesh({"sp": 8}, jax.devices()[:8])


def test_ring_attention_matches_full(mesh8):
    import jax.numpy as jnp
    from raydp_tpu.parallel import full_attention, ring_attention_sharded

    rng = np.random.default_rng(0)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 4, 64, 8)), jnp.float32)
        for _ in range(3)
    )
    for causal in (False, True):
        ref = full_attention(q, k, v, causal=causal)
        out = ring_attention_sharded(q, k, v, mesh8, axis="sp", causal=causal)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_with_flash_blocks(mesh8):
    """Ring attention computing each block product with the fused pallas
    flash kernel (ROADMAP item 2): exact vs full attention."""
    import jax.numpy as jnp

    from raydp_tpu.parallel import full_attention, ring_attention_sharded

    rng = np.random.default_rng(14)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 4, 256, 16)), jnp.float32)
        for _ in range(3)
    )
    for causal in (False, True):
        ref = full_attention(q, k, v, causal=causal)
        out = ring_attention_sharded(
            q, k, v, mesh8, axis="sp", causal=causal, use_flash=True
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)


def test_ulysses_attention_matches_full(mesh8):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel import full_attention, ulysses_attention

    rng = np.random.default_rng(1)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 8, 64, 8)), jnp.float32)
        for _ in range(3)
    )
    spec = P(None, None, "sp", None)
    out = jax.shard_map(
        partial(ulysses_attention, axis_name="sp", causal=True),
        mesh=mesh8, in_specs=(spec,) * 3, out_specs=spec,
    )(q, k, v)
    ref = full_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_dot_interaction_pallas_matches_xla():
    import jax.numpy as jnp
    from raydp_tpu.ops import dot_interaction, dot_interaction_pallas

    rng = np.random.default_rng(2)
    stacked = jnp.asarray(rng.standard_normal((36, 9, 16)), jnp.float32)
    ref = dot_interaction(stacked)
    assert ref.shape == (36, 36)  # 9*8/2
    out = dot_interaction_pallas(stacked, block_batch=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


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


def test_transformer_flash_matches_full():
    import jax
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM

    tokens = jnp.asarray(
        np.random.default_rng(8).integers(0, 50, size=(2, 128)), jnp.int32
    )
    full = TransformerLM(
        vocab_size=50, d_model=32, num_heads=4, num_layers=2, max_len=128,
        attn_impl="full", dtype=jnp.float32,
    )
    params = full.init(jax.random.PRNGKey(0), tokens)
    import dataclasses

    flash = dataclasses.replace(full, attn_impl="flash")
    np.testing.assert_allclose(
        np.asarray(flash.apply(params, tokens)),
        np.asarray(full.apply(params, tokens)),
        atol=2e-3,
    )


def test_sharded_embedding_lookup(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp
    from raydp_tpu.ops import sharded_embedding_lookup
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"model": 8}, jax.devices()[:8])
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, 64, size=(4, 5)), jnp.int32)
    out = sharded_embedding_lookup(table, ids, mesh, axis="model")
    ref = jnp.take(table, ids, axis=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)


def test_dlrm_forward_and_sharded_tables(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raydp_tpu.models import DLRM, dlrm_sharding_rules
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 4, "model": 2}, jax.devices()[:8])
    vocab_sizes = [32, 64, 16]
    model = DLRM(vocab_sizes=vocab_sizes, num_dense=4, embed_dim=8)
    rng = np.random.default_rng(4)
    dense = rng.random((16, 4)).astype(np.float32)
    ids = rng.integers(0, 16, size=(16, 3)).astype(np.float32)
    x = jnp.asarray(np.concatenate([dense, ids], axis=1))
    params = model.init(jax.random.PRNGKey(0), x)

    shardings = dlrm_sharding_rules()(mesh, params)
    params_sharded = jax.device_put(params, shardings)
    # table actually sharded over model axis
    table = params_sharded["params"]["embedding_0"]
    assert table.sharding.spec == P("model", None)

    with jax.set_mesh(mesh):
        out = jax.jit(model.apply)(params_sharded, x)
    assert out.shape == (16, 1)
    ref = model.apply(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.slow
def test_transformer_ring_matches_full(mesh8):
    import jax
    import jax.numpy as jnp

    from raydp_tpu.models import TransformerLM, sequence_parallel_apply

    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, 50, size=(2, 64)), jnp.int32)
    full = TransformerLM(
        vocab_size=50, d_model=32, num_heads=8, num_layers=2, max_len=64,
        attn_impl="full", dtype=jnp.float32,
    )
    params = full.init(jax.random.PRNGKey(0), tokens)
    ref = full.apply(params, tokens)

    ring = TransformerLM(
        vocab_size=50, d_model=32, num_heads=8, num_layers=2, max_len=64,
        attn_impl="ring", dtype=jnp.float32,
    )
    out = sequence_parallel_apply(ring, params, tokens, mesh8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-4)


def test_transformer_train_step_sp(mesh8):
    """One optimization step with sequence parallelism: loss finite, grads flow."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.models import TransformerLM, sequence_parallel_apply

    model = TransformerLM(
        vocab_size=50, d_model=32, num_heads=8, num_layers=1, max_len=64,
        attn_impl="ring", dtype=jnp.float32,
    )
    tokens = jnp.asarray(
        np.random.default_rng(6).integers(0, 50, size=(2, 64)), jnp.int32
    )
    # init outside shard_map needs an axis-free twin (same param structure)
    import dataclasses

    init_model = dataclasses.replace(model, attn_impl="full")
    params = init_model.init(jax.random.PRNGKey(0), tokens[:, :8])
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, tokens):
        def loss_fn(p):
            logits = sequence_parallel_apply(model, p, tokens[:, :-1], mesh8)
            targets = tokens[:, 1:]
            return jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(logits, targets)
            )

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    # 64-1 = 63 tokens does not divide 8 — pad to 64 with a wrap token
    padded = jnp.concatenate([tokens, tokens[:, :1]], axis=1)
    params, opt_state, loss = step(params, opt_state, padded)
    assert np.isfinite(float(loss))


def test_pipeline_parallel_matches_sequential(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel import make_mesh, pipeline_sharded

    mesh = make_mesh({"pp": 4}, jax.devices()[:4])
    rng = np.random.default_rng(9)
    D = 16
    Ws = jnp.asarray(rng.standard_normal((4, D, D)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((32, D)), jnp.float32)

    def stage_fn(W, t):
        return jax.nn.relu(t @ W)

    ref = x
    for i in range(4):
        ref = stage_fn(Ws[i], ref)
    out = pipeline_sharded(stage_fn, Ws, x, mesh, num_microbatches=8)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    grad = jax.grad(
        lambda w: jnp.sum(pipeline_sharded(stage_fn, w, x, mesh, 8) ** 2)
    )(Ws)

    def seq_loss(w):
        y = x
        for i in range(4):
            y = stage_fn(w[i], y)
        return jnp.sum(y**2)

    ref_grad = jax.grad(seq_loss)(Ws)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad), atol=1e-4)


def test_moe_expert_parallel_matches_dense(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel import make_mesh, moe_sharded

    N, D, B = 4, 8, 64
    mesh = make_mesh({"ep": N}, jax.devices()[:N])
    rng = np.random.default_rng(10)
    Ws = jnp.asarray(rng.standard_normal((N, D, D)) * 0.5, jnp.float32)
    Wr = jnp.asarray(rng.standard_normal((D, N)) * 0.5, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)

    def expert_fn(W, t):
        return jax.nn.relu(t @ W)

    gates = jax.nn.softmax(x @ Wr, -1)
    assign = jnp.argmax(gates, -1)
    gate = jnp.take_along_axis(gates, assign[:, None], 1)[:, 0]
    dense = jnp.stack([expert_fn(Ws[e], x) for e in range(N)], 1)
    ref = dense[jnp.arange(B), assign] * gate[:, None]

    out = moe_sharded(expert_fn, Ws, Wr, x, mesh, capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    # gradients through the double all_to_all + dispatch einsums
    grad = jax.grad(
        lambda w: jnp.sum(moe_sharded(expert_fn, w, Wr, x, mesh, capacity_factor=8.0) ** 2)
    )(Ws)

    def dense_loss(w):
        d = jnp.stack([expert_fn(w[e], x) for e in range(N)], 1)
        return jnp.sum((d[jnp.arange(B), assign] * gate[:, None]) ** 2)

    ref_grad = jax.grad(dense_loss)(Ws)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad), atol=1e-4)


def test_moe_top2_matches_dense(cpu_mesh_devices):
    """Top-2 routing with renormalized gates must equal the dense two-expert
    mixture when capacity is ample, and expose aux stats."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel import make_mesh, moe_sharded

    N, D, B = 4, 8, 64
    mesh = make_mesh({"ep": N}, jax.devices()[:N])
    rng = np.random.default_rng(21)
    Ws = jnp.asarray(rng.standard_normal((N, D, D)) * 0.5, jnp.float32)
    Wr = jnp.asarray(rng.standard_normal((D, N)) * 0.5, jnp.float32)
    x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)

    def expert_fn(W, t):
        return jax.nn.relu(t @ W)

    gates = jax.nn.softmax(x @ Wr, -1)
    top_vals, top_idx = jax.lax.top_k(gates, 2)
    w = top_vals / jnp.sum(top_vals, -1, keepdims=True)
    dense = jnp.stack([expert_fn(Ws[e], x) for e in range(N)], 1)  # [B,N,D]
    ref = (
        dense[jnp.arange(B), top_idx[:, 0]] * w[:, :1]
        + dense[jnp.arange(B), top_idx[:, 1]] * w[:, 1:]
    )

    out, aux = moe_sharded(
        expert_fn, Ws, Wr, x, mesh, capacity_factor=8.0, top_k=2,
        return_aux=True,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert float(aux["drop_fraction"]) == 0.0  # ample capacity
    assert float(aux["load_balance_loss"]) >= 1.0  # ==1 only at perfect balance

    # gradients flow through the top-2 combine
    grad = jax.grad(
        lambda ws: jnp.sum(
            moe_sharded(expert_fn, ws, Wr, x, mesh, capacity_factor=8.0, top_k=2) ** 2
        )
    )(Ws)

    def dense_loss(ws):
        d = jnp.stack([expert_fn(ws[e], x) for e in range(N)], 1)
        o = (
            d[jnp.arange(B), top_idx[:, 0]] * w[:, :1]
            + d[jnp.arange(B), top_idx[:, 1]] * w[:, 1:]
        )
        return jnp.sum(o ** 2)

    ref_grad = jax.grad(dense_loss)(Ws)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad), atol=1e-4)


def test_moe_drop_fraction_visible(cpu_mesh_devices):
    """Tokens beyond capacity are dropped — round 1 did this silently; the
    drop fraction must now be reported."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel import make_mesh, moe_sharded

    N, D, B = 4, 8, 64
    mesh = make_mesh({"ep": N}, jax.devices()[:N])
    rng = np.random.default_rng(22)
    Ws = jnp.asarray(rng.standard_normal((N, D, D)), jnp.float32)
    # router biased hard toward expert 0 → guaranteed overflow at cf=1.0
    Wr = jnp.asarray(
        np.concatenate(
            [np.full((D, 1), 3.0), np.zeros((D, N - 1))], axis=1
        ),
        jnp.float32,
    )
    x = jnp.abs(jnp.asarray(rng.standard_normal((B, D)), jnp.float32))

    _, aux = moe_sharded(
        lambda W, t: t @ W, Ws, Wr, x, mesh, capacity_factor=1.0, top_k=1,
        return_aux=True,
    )
    assert float(aux["drop_fraction"]) > 0.2
    assert float(aux["load_balance_loss"]) > 1.5  # collapsed router


def test_moe_aux_loss_reduces_imbalance(cpu_mesh_devices):
    """Training the router against load_balance_loss must spread the load:
    the loss falls toward 1.0 (perfect balance) and drops disappear."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.parallel import make_mesh, moe_sharded

    N, D, B = 4, 8, 64
    mesh = make_mesh({"ep": N}, jax.devices()[:N])
    rng = np.random.default_rng(23)
    Ws = jnp.asarray(rng.standard_normal((N, D, D)) * 0.5, jnp.float32)
    # collapsed start: every token prefers expert 0
    Wr0 = jnp.asarray(
        np.concatenate([np.full((D, 1), 2.0), np.zeros((D, N - 1))], 1)
        + rng.standard_normal((D, N)) * 0.01,
        jnp.float32,
    )
    x = jnp.abs(jnp.asarray(rng.standard_normal((B, D)), jnp.float32))

    def aux_of(wr):
        _, aux = moe_sharded(
            lambda W, t: t @ W, Ws, wr, x, mesh, capacity_factor=1.25,
            top_k=2, return_aux=True,
        )
        return aux["load_balance_loss"], aux["drop_fraction"]

    tx = optax.adam(0.05)
    opt_state = tx.init(Wr0)

    @jax.jit
    def step(wr, opt_state):
        lb, _ = aux_of(wr)
        g = jax.grad(lambda w: aux_of(w)[0])(wr)
        updates, opt_state = tx.update(g, opt_state, wr)
        return optax.apply_updates(wr, updates), opt_state, lb

    wr = Wr0
    lb_first = None
    for _ in range(120):
        wr, opt_state, lb = step(wr, opt_state)
        if lb_first is None:
            lb_first = float(lb)
    lb_last, drop_last = (float(v) for v in aux_of(wr))
    assert lb_first > 1.5, f"start not collapsed: {lb_first}"
    assert lb_last < 1.15, f"aux loss failed to rebalance: {lb_last}"
    assert drop_last < 0.05, f"drops persist after rebalancing: {drop_last}"


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


def test_quantize_int8_roundtrip():
    import jax.numpy as jnp

    from raydp_tpu.ops import dequantize_int8, quantize_int8

    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((32, 128)) * 2, jnp.float32)
    values, scales = quantize_int8(x)
    assert values.dtype == jnp.int8 and scales.shape == (32, 1)
    back = dequantize_int8(values, scales)
    quantum = float(jnp.max(scales))
    assert float(jnp.max(jnp.abs(back - x))) <= quantum + 1e-6

    # stochastic path (jax.random off-TPU; the pallas kernel is TPU-only and
    # validated on real hardware): unbiased
    sv, ss = quantize_int8(x, seed=3, stochastic=True)
    sback = dequantize_int8(sv, ss)
    assert abs(float(jnp.mean(sback - x))) < quantum / 10


def test_int8_matmul_and_quantized_mlp():
    """int8_matmul: forward approximates the float matmul within the
    per-row/column quantization bound; gradients are the exact-matmul
    straight-through grads. The quantized_mlp model flag keeps the SAME
    param tree as the bf16 path (nn.Dense with a custom dot_general), so
    checkpoints interchange; training through it converges."""
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.models import TransformerLM
    from raydp_tpu.ops import int8_matmul

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128, 96)) * 0.1, jnp.float32)
    y = int8_matmul(x, w)
    ref = x @ w
    assert float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref))) < 0.03
    gx, gw = jax.grad(lambda a, b: jnp.sum(int8_matmul(a, b) ** 2), (0, 1))(x, w)
    assert gx.shape == x.shape and gw.shape == w.shape
    assert np.isfinite(np.asarray(gw)).all()

    # identical param trees: a bf16 checkpoint loads into the int8 model
    kw = dict(
        vocab_size=64, d_model=64, num_heads=4, num_layers=2, max_len=64,
        dtype=jnp.float32,
    )
    tok = jnp.asarray(rng.integers(0, 64, (2, 32)), jnp.int32)
    p_plain = TransformerLM(**kw).init(jax.random.PRNGKey(0), tok)
    quant = TransformerLM(quantized_mlp=True, **kw)
    p_quant = quant.init(jax.random.PRNGKey(0), tok)
    assert jax.tree.structure(p_plain) == jax.tree.structure(p_quant)
    quant.apply(p_plain, tok)  # bf16-trained params run on the int8 path

    # training converges through the straight-through estimator
    tx = optax.adam(3e-3)
    p, o = p_quant, tx.init(p_quant)

    @jax.jit
    def step(p, o):
        def f(pp):
            lg = quant.apply(pp, tok)
            return optax.softmax_cross_entropy_with_integer_labels(
                lg, jnp.roll(tok, -1, 1)
            ).mean()

        l, g = jax.value_and_grad(f)(p)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, l

    l0 = None
    for _ in range(60):
        p, o, l = step(p, o)
        if l0 is None:
            l0 = float(l)
    assert float(l) < l0 * 0.5


def test_make_mesh_shapes(cpu_mesh_devices):
    import jax
    from raydp_tpu.parallel import make_mesh, mesh_axis_size

    mesh = make_mesh({"data": -1}, jax.devices()[:8])
    assert mesh_axis_size(mesh, "data") == 8
    mesh = make_mesh({"data": 2, "model": -1}, jax.devices()[:8])
    assert mesh.shape["model"] == 4
    with pytest.raises(ValueError):
        make_mesh({"data": 16}, jax.devices()[:8])


def test_ring_attention_backward_matches_full(mesh8):
    """The ring-attention custom VJP (second ring pass rotating dk/dv with
    their K/V blocks, probabilities rebuilt from the global logsumexp) must
    match gradients through single-device full attention — einsum AND flash
    block kernels, causal and not."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel import full_attention, ring_attention_sharded

    rng = np.random.default_rng(31)
    q, k, v = (
        jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)
        for _ in range(3)
    )
    g = jnp.asarray(rng.standard_normal((1, 2, 256, 16)), jnp.float32)

    for causal in (False, True):
        _, ref_vjp = jax.vjp(
            lambda a, b, c: full_attention(a, b, c, causal=causal), q, k, v
        )
        ref_grads = ref_vjp(g)
        for use_flash in (False, True):
            _, vjp = jax.vjp(
                lambda a, b, c: ring_attention_sharded(
                    a, b, c, mesh8, axis="sp", causal=causal,
                    use_flash=use_flash,
                ),
                q, k, v,
            )
            grads = vjp(g)
            for name, got, want in zip(("dq", "dk", "dv"), grads, ref_grads):
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), atol=2e-4,
                    err_msg=f"causal={causal} flash={use_flash} {name}",
                )


def test_ulysses_flash_matches_full(mesh8):
    """Ulysses with the fused flash kernel on the gathered local sequence —
    exact vs full attention, forward and backward."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel import full_attention, ulysses_attention

    rng = np.random.default_rng(4)
    q, k, v = (
        jnp.asarray(rng.standard_normal((2, 8, 64, 8)), jnp.float32)
        for _ in range(3)
    )
    spec = P(None, None, "sp", None)
    fn = jax.shard_map(
        partial(ulysses_attention, axis_name="sp", causal=True, use_flash=True),
        mesh=mesh8, in_specs=(spec,) * 3, out_specs=spec, check_vma=False,
    )
    out, vjp = jax.vjp(fn, q, k, v)
    ref, rvjp = jax.vjp(partial(full_attention, causal=True), q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-5)
    g = jnp.asarray(rng.standard_normal(out.shape), jnp.float32)
    for a, b in zip(vjp(g), rvjp(g)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)
