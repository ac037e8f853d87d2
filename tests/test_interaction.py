"""The DLRM interaction on its feature-major operand (ops/interaction.py): a
sample's ``F`` vectors as ``[F, D, B]``, the batch on the lanes. The Mosaic
kernel runs interpreted here; what it compiles to for a chip is
tests/test_tpu_compile_dlrm.py's."""

import numpy as np
import pytest


def _vectors(b, f, d, dtype, seed=0):
    import jax.numpy as jnp

    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((b, f, d)), jnp.float32).astype(dtype)


def _einsum(stacked):
    """The parent's ``dot_interaction``, written out: ``[B, F, D]`` in
    float32, the strict lower triangle row by row."""
    import jax.numpy as jnp

    stacked = stacked.astype(jnp.float32)
    rows, cols = np.tril_indices(stacked.shape[1], k=-1)
    return jnp.einsum("bfd,bgd->bfg", stacked, stacked)[:, rows, cols]


@pytest.mark.parametrize("b, f, d, dtype", [
    (2048, 27, 16, "float32"),  # the DLRM cells' operand
    (200, 27, 16, "float32"),   # a batch that fills no lane tile
    (256, 4, 8, "float32"),     # six pairs, one sublane tile a slab
    (256, 27, 32, "bfloat16"),
])
def test_feature_major_kernel_and_its_gradient_match_the_einsum(b, f, d, dtype):
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops.interaction import interaction_pallas, interaction_xla

    stacked = _vectors(b, f, d, dtype)
    slabs = jnp.transpose(stacked, (1, 2, 0))
    want = _einsum(stacked)
    got = interaction_pallas(slabs, None, True)
    assert got.shape == (b, f * (f - 1) // 2) and got.dtype == stacked.dtype
    # float32 accumulation whatever the operand: what is left is the
    # result's own rounding
    eps = float(jnp.finfo(stacked.dtype).eps)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want), atol=eps * scale)
    np.testing.assert_allclose(
        np.asarray(interaction_xla(slabs), np.float32), np.asarray(want),
        atol=(4 if dtype == "bfloat16" else 1) * eps * scale)

    weight = jnp.asarray(
        np.random.default_rng(1).standard_normal(want.shape), jnp.float32)

    def loss(fn, operand):
        return (fn(operand).astype(jnp.float32) ** 2 * weight).sum()

    want_grad = jax.grad(lambda s: loss(_einsum, s))(stacked.astype(jnp.float32))
    got_grad = jax.grad(
        lambda s: loss(lambda t: interaction_pallas(t, None, True), s))(slabs)
    assert got_grad.shape == slabs.shape and got_grad.dtype == slabs.dtype
    # the cotangent is feature-major too; a bfloat16 operand squares a
    # rounded forward result, so its gradient carries that rounding twice
    np.testing.assert_allclose(
        np.asarray(jnp.transpose(got_grad, (2, 0, 1)), np.float32),
        np.asarray(want_grad),
        atol=(8 if dtype == "bfloat16" else 4) * eps
        * float(jnp.abs(want_grad).max()))


@pytest.mark.parametrize("entry", [
    "dot_interaction", "dot_interaction_pallas", "dot_interaction_fused"])
def test_batch_major_entries_return_what_they_returned(entry):
    """``[B, F, D]`` in, ``[B, F (F - 1) / 2]`` out, as before the operand
    turned feature-major. ``dot_interaction`` is the parent's einsum, bit for
    bit. The kernel turns its tile in VMEM and runs the parent kernel's
    batched product on it (on the chip the two give the same bits: PERF.md,
    Findings, PR 47; the interpreter's product is held to 1e-6 of the largest
    dot here); ``dot_interaction_fused`` with eight devices and no mesh
    context is the einsum on the transposed operand: the same bound."""
    import jax

    from raydp_tpu.ops import interaction

    stacked = _vectors(36, 9, 16, "float32", seed=2)
    want = np.asarray(_einsum(stacked))
    fn = getattr(interaction, entry)
    got = np.asarray(
        fn(stacked) if entry != "dot_interaction_pallas"
        else fn(stacked, 16, True))
    assert got.shape == (36, 36)
    if entry == "dot_interaction":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, atol=1e-6 * np.abs(want).max())
    # and under jit with the defaults, as benchmark/tests/record_trace.py
    # and chip_smoke.py call them
    np.testing.assert_allclose(
        np.asarray(jax.jit(fn)(stacked)), want, atol=1e-6 * np.abs(want).max())


def test_fused_kernel_runs_per_shard_with_the_batch_axis_last(cpu_mesh_devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from raydp_tpu.ops.interaction import interaction_fused
    from raydp_tpu.parallel import make_mesh

    mesh = make_mesh({"data": 4, "model": 2}, cpu_mesh_devices[:8])
    stacked = _vectors(64, 5, 8, "float32", seed=3)
    slabs = jax.device_put(
        jnp.transpose(stacked, (1, 2, 0)),
        NamedSharding(mesh, P(None, None, "data")))
    weight = jnp.asarray(
        np.random.default_rng(4).standard_normal((64, 10)), jnp.float32)
    with jax.set_mesh(mesh):
        out = jax.jit(interaction_fused)(slabs)
        text = str(jax.make_jaxpr(interaction_fused)(slabs))
        grad = jax.jit(jax.grad(
            lambda s: (interaction_fused(s) ** 2 * weight).sum()))(slabs)
    # the kernel, not the einsum it falls back to without a mesh
    assert "shard_map" in text and "pallas_call" in text
    assert tuple(out.sharding.spec)[0] == "data"
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_einsum(stacked)), atol=1e-5)
    want = jax.grad(lambda s: (_einsum(s) ** 2 * weight).sum())(stacked)
    np.testing.assert_allclose(
        np.asarray(jnp.transpose(grad, (2, 0, 1))), np.asarray(want), atol=1e-4)


def _dlrm(**kw):
    from raydp_tpu.models import DLRM

    return DLRM(**{**dict(
        vocab_sizes=(50, 7, 300), num_dense=4, embed_dim=8, bottom_mlp=(16,),
        top_mlp=(16,)), **kw})


@pytest.mark.parametrize("kernel", [False, True])
def test_dlrm_logits_are_the_same_with_rows_handed_in(kernel):
    """``rows=`` (the row-wise step's way in) against the model's own takes,
    on the XLA path and through the interpreted kernel (a mesh of one
    device: with eight and no mesh the fused entry is the einsum)."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.parallel import make_mesh

    model = _dlrm(use_pallas_interaction=kernel)
    rng = np.random.default_rng(5)
    x = (jnp.asarray(rng.normal(size=(24, 4)), jnp.float32),
         jnp.asarray(np.stack([rng.integers(0, v, 24)
                               for v in model.vocab_sizes], 1), jnp.int32))
    params = model.init(jax.random.PRNGKey(0), x)
    ids = model.row_gathers(x)
    handed = {p: params[p[0]][p[1]][i] for p, i in ids.items()
              if p[1] != "embedding_1"}
    with jax.set_mesh(make_mesh({"data": 1}, jax.devices()[:1])):
        own = model.apply(params, x)
        given = model.apply(params, x, rows=handed)
        text = str(jax.make_jaxpr(model.apply)(params, x))
    assert ("pallas_call" in text) == kernel
    assert own.shape == (24, 1)
    np.testing.assert_array_equal(np.asarray(own), np.asarray(given))
    if kernel:
        np.testing.assert_allclose(
            np.asarray(own),
            np.asarray(_dlrm(use_pallas_interaction=False).apply(params, x)),
            atol=1e-5)


@pytest.mark.parametrize("kw, kernel, why", [
    ({}, "xla", "no TPU"),  # left to the backend, which is the CPU here
    ({"use_pallas_interaction": False}, "xla", "use_pallas_interaction"),
    ({"use_pallas_interaction": True}, "mosaic", None),
    ({"use_pallas_interaction": True, "embed_dim": 12}, "xla", "multiple of 8"),
    ({"use_pallas_interaction": True, "dtype": "bfloat16"}, "xla",
     "multiple of 16"),
    ({"use_pallas_interaction": True, "dtype": "bfloat16", "embed_dim": 32},
     "mosaic", None),
])
def test_fit_facts_say_the_operand_and_the_kernel(kw, kernel, why):
    import jax.numpy as jnp

    if "dtype" in kw:
        kw = {**kw, "dtype": jnp.dtype(kw["dtype"])}
    facts = _dlrm(**kw).fit_facts(None)
    assert facts["interaction_operand"] == "feature_major"
    assert facts["interaction_kernel"] == kernel
    assert facts["interaction.row_blocks"] == 4
    assert "flops_per_row" not in facts  # the FLOPs probe keeps running
    if why is None:
        assert "interaction_kernel_reason" not in facts
    else:
        assert why in facts["interaction_kernel_reason"]


def test_a_width_the_kernel_does_not_take_runs_on_the_xla_path():
    """An embedding of 12 numbers is no whole sublane tile: the model falls
    back to XLA and gives the same logits as when asked for XLA."""
    import jax
    import jax.numpy as jnp

    from raydp_tpu.ops.interaction import interaction_pallas, supports
    from raydp_tpu.parallel import make_mesh

    assert "12" in supports(12, jnp.float32) and not supports(16, jnp.float32)
    with pytest.raises(ValueError, match="multiple of 8"):
        interaction_pallas(jnp.zeros((3, 12, 128), jnp.float32), None, True)
    rng = np.random.default_rng(6)
    x = (jnp.asarray(rng.normal(size=(8, 4)), jnp.float32),
         jnp.asarray(rng.integers(0, 7, (8, 3)), jnp.int32))
    asked, plain = (_dlrm(embed_dim=12, use_pallas_interaction=k)
                    for k in (True, False))
    params = plain.init(jax.random.PRNGKey(0), x)
    with jax.set_mesh(make_mesh({"data": 1}, jax.devices()[:1])):
        np.testing.assert_array_equal(
            np.asarray(asked.apply(params, x)), np.asarray(plain.apply(params, x)))
