"""MLP models — the NYCTaxi workload family (reference
examples/pytorch_nyctaxi.py builds a 5-layer torch MLP; this is the flax
equivalent used by examples and tests)."""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax.numpy as jnp


class MLPRegressor(nn.Module):
    """Dense → relu stack → scalar head. hidden=(256,128,64,16) matches the
    reference NYCTaxi model's widths (examples/pytorch_nyctaxi.py:34-49)."""

    hidden: Sequence[int] = (256, 128, 64, 16)
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        for width in self.hidden:
            x = nn.relu(nn.Dense(width, dtype=self.dtype)(x))
        return nn.Dense(1, dtype=self.dtype)(x)


class MLPClassifier(nn.Module):
    hidden: Sequence[int] = (256, 128, 64)
    num_classes: int = 2
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        x = x.astype(self.dtype)
        for width in self.hidden:
            x = nn.relu(nn.Dense(width, dtype=self.dtype)(x))
        return nn.Dense(self.num_classes, dtype=self.dtype)(x)
