"""Long-context LM training with ring attention: the sequence is sharded over
every device; each device holds T/N tokens and K/V blocks rotate over ICI.
Nothing like this exists in the reference — long context is first-class here.

This script drives a hand-written ``jax.jit`` step. An LM that trains through
``JaxEstimator.fit`` from an ETL frame (a ``FixedSizeList<int32>`` sequence
column, ``loss="model"``) is in docs/estimators.md, "Language models".

Run under a CPU mesh for demonstration:
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/long_context_lm.py
"""
# raydp-lint: disable-file=print-diagnostics  (examples narrate to stdout by design — they run standalone, before any obs plane exists)

import dataclasses

import numpy as np


def main():
    import jax
    import jax.numpy as jnp
    import optax

    from raydp_tpu.models import TransformerLM, sequence_parallel_apply
    from raydp_tpu.parallel import make_mesh

    n_dev = len(jax.devices())
    mesh = make_mesh({"sp": n_dev})
    seq = 128 * n_dev  # a sequence n_dev× longer than one device's share

    model = TransformerLM(
        vocab_size=256, d_model=128, num_heads=n_dev, num_layers=2,
        max_len=seq, attn_impl="ring", dtype=jnp.float32,
    )
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, 256, size=(2, seq)), jnp.int32)

    params = dataclasses.replace(model, attn_impl="full").init(
        jax.random.PRNGKey(0), tokens[:, :16]
    )
    tx = optax.adam(3e-4)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, tokens):
        def loss_fn(p):
            logits = sequence_parallel_apply(model, p, tokens, mesh)
            shifted = jnp.roll(tokens, -1, axis=1)
            return jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(logits, shifted)
            )

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for i in range(5):
        params, opt_state, loss = step(params, opt_state, tokens)
        print(f"step {i}: loss {float(loss):.4f} (seq={seq} over {n_dev} devices)")


if __name__ == "__main__":
    main()
