"""DLRM forward, loss and gradients, written from the model's description
(Naumov et al., arXiv:1906.00091; ``facebookresearch/dlrm``), against the
parameter tree ``raydp_tpu.models.DLRM`` creates.

Departures of the program's model from the source, kept here so that program
and reference agree (they are listed in the configuration file too):
the bottom MLP ends in a linear projection to ``embed_dim`` (no ReLU after
it); the interaction is the strict lower triangle of the Gram matrix of the
(1 + tables) vectors (the source's default, ``--arch-interaction-itself``
off); the top MLP's last layer is linear and the loss takes logits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _dense(p, x):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def forward(params, dense, ids, num_bottom: int, num_top: int):
    """Logits [B, 1]. ``params`` is the flax tree (``{"params": {...}}``):
    ``Dense_0..`` the bottom layers then the top layers in creation order,
    ``bottom_proj``, ``embedding_<i>``, ``head``."""
    p = params["params"]
    with jax.default_matmul_precision("highest"):
        h = dense.astype(jnp.float32)
        for i in range(num_bottom):
            h = jax.nn.relu(_dense(p[f"Dense_{i}"], h))
        h = _dense(p["bottom_proj"], h)
        vectors = [h]
        tables = sorted(
            (k for k in p if k.startswith("embedding_")),
            key=lambda k: int(k.split("_")[1]))
        for i, name in enumerate(tables):
            table = p[name].astype(jnp.float32)
            row = jnp.clip(ids[:, i].astype(jnp.int32), 0, table.shape[0] - 1)
            vectors.append(table[row])
        t = jnp.stack(vectors, axis=1)  # [B, F, D]
        gram = jnp.einsum("bfd,bgd->bfg", t, t)
        rows, cols = np.tril_indices(t.shape[1], k=-1)
        z = jnp.concatenate([h, gram[:, rows, cols]], axis=1)
        for i in range(num_bottom, num_bottom + num_top):
            z = jax.nn.relu(_dense(p[f"Dense_{i}"], z))
        return _dense(p["head"], z)


def bce_with_logits(logits, labels):
    """Mean binary cross-entropy on logits: log(1 + e^x) - x*y, with the
    softplus as ``logaddexp(x, 0)`` (stable for large |x|, and smooth at
    x = 0, where a max/abs spelling has a kink whose subgradient a dead
    network's all-zero logits would land on)."""
    x = logits.reshape(labels.shape).astype(jnp.float32)
    y = labels.astype(jnp.float32)
    return jnp.mean(jnp.logaddexp(x, 0.0) - x * y)


def loss_and_grads(params, dense, ids, labels, num_bottom: int, num_top: int):
    def loss(p):
        logits = forward(p, dense, ids, num_bottom, num_top)
        return bce_with_logits(logits, labels), logits

    (value, logits), grads = jax.value_and_grad(loss, has_aux=True)(params)
    return value, logits, grads
