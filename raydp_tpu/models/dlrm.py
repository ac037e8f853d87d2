"""DLRM — the Criteo workload (reference examples/pytorch_dlrm.ipynb "DLRM
Model" cells: bottom MLP over dense features, one embedding table per
categorical feature, pairwise dot interaction, top MLP).

TPU-first differences from the reference:
- embedding tables are **vocab-sharded over the "model" mesh axis** via
  NamedSharding rules (``dlrm_sharding_rules``) — XLA partitions the gathers
  and inserts the collectives (the reference trains pure-DP with replicated
  tables; BASELINE.md asks for sharded);
- the interaction is the op from raydp_tpu.ops.interaction on a
  FEATURE-MAJOR operand: the bottom MLP's output and every table's rows as
  ``[embed_dim, B]`` slabs, the batch on the lanes, stacked to ``[1 + S,
  embed_dim, B]``. A ``[B, embed_dim]`` block pads its 16 columns to 128
  lanes on the chip, and as a ``[B, 1, embed_dim]`` block of a batch-major
  stack 8- to 128-fold: the step spent 37 % of its time on such blocks
  (PERF.md, Findings, PR 47). The Mosaic kernel on a TPU, an einsum elsewhere
  and wherever the kernel does not take the width (``fit_facts`` says which);
- bfloat16 compute path for the MXU via ``dtype=jnp.bfloat16``.

Input convention — two forms:
- preferred (the estimator's ``categorical_columns`` mixed-dtype path):
  ``x = (dense, ids)`` with dense float [B, num_dense] and ids integer
  [B, S] — exact at ANY vocab size (reference pytorch_dlrm.ipynb feeds
  int64 ids through torch tensors; this is the jax-native equivalent);
- legacy single float matrix: x[:, :num_dense] dense, x[:, num_dense:]
  categorical ids cast back to int32 (guarded — float32 represents
  integers exactly only up to 2^24, so big vocabs must use the tuple form).
"""

from __future__ import annotations

from typing import Optional, Sequence

import flax.linen as nn
import jax.numpy as jnp

from raydp_tpu import obs
from raydp_tpu.ops.backend import on_tpu
from raydp_tpu.ops.interaction import interaction_fused, interaction_xla, supports


class DLRM(nn.Module):
    vocab_sizes: Sequence[int]
    num_dense: int
    embed_dim: int = 16
    bottom_mlp: Sequence[int] = (64, 32)
    top_mlp: Sequence[int] = (64, 32)
    use_pallas_interaction: Optional[bool] = None  # None = pallas on TPU
    dtype: jnp.dtype = jnp.float32

    def _check_float_ids(self, dtype) -> None:
        """Trace-time guard: floats represent integers exactly only up to
        2^mantissa — beyond that, distinct ids silently collapse onto the
        same embedding row (dtype and vocab sizes are static)."""
        if not jnp.issubdtype(dtype, jnp.floating):
            return
        mantissa = jnp.finfo(dtype).nmant + 1
        max_vocab = max(self.vocab_sizes)
        # ints up to 2^mantissa INCLUSIVE are exact; max id is vocab-1
        if max_vocab - 1 > 2**mantissa:
            raise ValueError(
                f"vocab size {max_vocab} exceeds exact-integer range of "
                f"{dtype} ids (2^{mantissa}); pass ids as a separate integer "
                "array (JaxEstimator categorical_columns / x=(dense, ids))"
            )

    def _split(self, x):
        """``(dense, ids)`` of either input form; ``ids[i]`` is column ``i``
        as int32 [B], clipped to table ``i``'s rows."""
        if isinstance(x, (tuple, list)):
            # mixed-dtype input (dense, ids): integer ids are exact at any
            # vocab size; float ids get the same guard as the legacy path
            dense, ids = x
            self._check_float_ids(ids.dtype)
        else:
            self._check_float_ids(x.dtype)
            dense, ids = x[:, : self.num_dense], x[:, self.num_dense :]
        ids = ids.astype(jnp.int32)  # [B, S]
        return dense.astype(self.dtype), [
            jnp.clip(ids[:, i], 0, vocab - 1)
            for i, vocab in enumerate(self.vocab_sizes)
        ]

    def row_gathers(self, x):
        """The parameters ``__call__`` reads by rows only, and the row each
        sample of ``x`` reads of them: ``{path in the variables: ids [B]}``.
        A training step that finds this method may differentiate and update
        those rows alone (``JaxEstimator``, docs/estimators.md "Row-wise
        update"), handing them to ``__call__`` as ``rows``."""
        _, ids = self._split(x)
        return {("params", f"embedding_{i}"): col for i, col in enumerate(ids)}

    def _use_pallas(self) -> bool:
        if self.use_pallas_interaction is None:
            # the kernel's forward call takes 50 us for the einsum's 58 at
            # the Criteo shapes on a v5e, timed alone (PERF.md, Findings, PR
            # 47); multi-device meshes run it per-shard via shard_map
            # (interaction_fused) — the dp×tp path keeps the kernel
            return on_tpu()
        return self.use_pallas_interaction

    # -- what the estimator writes down once per fit (fit_facts rule) --------
    def fit_facts(self, x) -> dict:
        """The interaction as this fit runs it: the operand's layout (one for
        every caller), which path computes it (``mosaic``: the kernel;
        ``xla`` and why where it cannot run or is not asked for) and the row
        blocks a sample sends through it. No ``flops_per_row``: XLA's count
        of the step program stands."""
        if not self._use_pallas():
            why = ("the model's use_pallas_interaction is False"
                   if self.use_pallas_interaction is not None
                   else "the backend is no TPU")
        else:
            why = supports(self.embed_dim, self.dtype)
        facts = {
            "interaction_operand": "feature_major",
            "interaction_kernel": "xla" if why else "mosaic",
            "interaction.row_blocks": 1 + len(self.vocab_sizes),
        }
        if why:
            facts["interaction_kernel_reason"] = why
        return facts

    @nn.compact
    def __call__(self, x, rows=None):
        """``rows`` (optional): ``{path: [B, embed_dim]}`` for any of
        ``row_gathers(x)``'s paths, the rows already gathered at its ids;
        the table itself is then not read."""
        dense, ids = self._split(x)
        rows = rows or {}

        # bottom MLP → dense embedding of dim embed_dim
        h = dense
        for width in self.bottom_mlp:
            h = nn.relu(nn.Dense(width, dtype=self.dtype)(h))
        h = nn.Dense(self.embed_dim, dtype=self.dtype, name="bottom_proj")(h)

        # per-feature embedding tables (vocab-sharded under the rules below).
        # A sample's 1 + S vectors go to the interaction FEATURE-MAJOR, the
        # batch on the lanes: each a [D, B] slab of whole tiles, stacked
        # along a leading axis that moves none (ops/interaction.py)
        slabs = [h.T]
        for i, vocab in enumerate(self.vocab_sizes):
            table = self.param(
                f"embedding_{i}",
                nn.initializers.normal(stddev=1.0 / self.embed_dim**0.5),
                (vocab, self.embed_dim),
                jnp.float32,
            )
            given = rows.get(("params", f"embedding_{i}"))
            slabs.append((
                jnp.take(table.astype(self.dtype), ids[i], axis=0)
                if given is None
                else given.astype(self.dtype)
            ).T)
        t = jnp.stack(slabs)  # [1+S, D, B]

        # a stable name in the device trace, whichever path computes it
        with obs.device_scope("dlrm_interaction"):
            interact = (
                interaction_fused if self._use_pallas() else interaction_xla
            )(t)
        z = jnp.concatenate([h, interact.astype(self.dtype)], axis=1)

        for width in self.top_mlp:
            z = nn.relu(nn.Dense(width, dtype=self.dtype)(z))
        return nn.Dense(1, dtype=self.dtype, name="head")(z)


def dlrm_optimizer(embedding_lr: float = 1e-2, dense_lr: float = 1e-3):
    """The Criteo-scale optimizer: Adafactor for the embedding tables,
    Adam for everything else (``optax.multi_transform`` keyed on param
    names). Dense Adam keeps TWO full-table moment copies — at a 2^25-row
    table that is 4.3GB of extra HBM and enough, with the dense gradient,
    to overflow a v5e chip (measured: OOM, or ~0.4s/step when it squeaks
    by). Adafactor with the factoring threshold lowered to cover embedding
    shapes keeps O(rows + cols) second-moment state: the same big-vocab
    step measures ~34ms (>10x) and fits comfortably. Pass the result as
    ``JaxEstimator(optimizer=dlrm_optimizer())``.

    It buys memory, not speed: every step still reads and writes every row
    of every table. Adafactor's factored statistics are shared between the
    rows of a table and Adam's moments decay at a zero gradient, so the
    estimator's row-wise update (docs/estimators.md "Row-wise update") does
    not engage under this optimizer, where it does under ``"adagrad"`` or
    ``"sgd"``: the fit observes that on a toy tree and keeps the dense step
    (``fit_stats_["row_update"]["reason"]``)."""
    import optax

    def label_fn(params):
        import flax

        flat = flax.traverse_util.flatten_dict(params)
        labels = {
            k: ("embed" if any("embedding_" in str(p) for p in k) else "dense")
            for k in flat
        }
        return flax.traverse_util.unflatten_dict(labels)

    return optax.multi_transform(
        {
            # min_dim_size_to_factor=0: optax only factors the second
            # moment when the smaller dim is >=128 by default — embedding
            # tables are [vocab, 16..64], so without this the "factored"
            # moment silently stays a full table copy
            "embed": optax.adafactor(embedding_lr, min_dim_size_to_factor=0),
            "dense": optax.adam(dense_lr),
        },
        label_fn,
    )


def dlrm_sharding_rules():
    """param_sharding_rules for JaxEstimator: embedding tables vocab-sharded
    over the "model" axis, everything else replicated."""
    from jax.sharding import PartitionSpec as P

    from raydp_tpu.parallel.sharding import sharding_rules_fn

    return sharding_rules_fn([(r"embedding_\d+", P("model", None))])
