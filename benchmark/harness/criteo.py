"""Criteo-shaped raw rows from a seed (numpy and Arrow only; no jax).

Thirteen integer count columns ``i0..i12`` and twenty-six categorical
columns ``c0..c25`` of 8-hex-digit strings, as in the Criteo Kaggle Display
Advertising files, and a ``label``. Column ``c<j>`` holds ``cardinality[j]``
distinct values whose ranks follow a bounded Zipf law, so a few values take
most rows and the large tables are touched sparsely, as in the real data.
The label is a Bernoulli draw of a logistic model over the log1p'd counts
and the ranks of the columns with few categories; the model's coefficients
are part of the traffic definition (fixed), the rows come from the seed.
Everything is vectorised: no Python loop over values.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

_HEX = np.frombuffer(b"0123456789abcdef", np.uint8)
_SHIFTS = np.arange(28, -4, -4, dtype=np.uint32)
LABEL_MODEL_SEED = 20230  # the label model is fixed; only rows follow --seed
SMALL_CARDINALITY = 1000


def bounded_zipf(rng, a: float, n: int, size: int) -> np.ndarray:
    """Ranks in [0, n) with P(rank r) roughly proportional to (r+1)^-a, by
    inverting the CDF of the continuous bounded power law."""
    u = rng.random(size)
    if abs(a - 1.0) < 1e-9:
        x = np.exp(u * np.log(n + 1.0))
    else:
        top = (n + 1.0) ** (1.0 - a)
        x = (1.0 + u * (top - 1.0)) ** (1.0 / (1.0 - a))
    return np.minimum(np.floor(x).astype(np.int64) - 1, n - 1).clip(0)


def raw_values(ranks: np.ndarray, column: int) -> np.ndarray:
    """Rank -> the raw 32-bit value the file would hold: a fixed bijection of
    the 32-bit integers per column (odd multiplier, column salt)."""
    mult = np.uint64(2654435761 + 2 * column)  # odd
    salt = np.uint64(0x9E3779B1 * (column + 1) & 0xFFFFFFFF)
    return ((ranks.astype(np.uint64) * mult + salt)
            & np.uint64(0xFFFFFFFF)).astype(np.uint32)


def hex_strings(values: np.ndarray) -> pa.Array:
    """uint32 -> Arrow string array of 8 lowercase hex digits, built from
    buffers (offsets are multiples of 8)."""
    digits = (values[:, None] >> _SHIFTS[None, :]) & np.uint32(0xF)
    data = _HEX[digits.astype(np.uint8)].reshape(-1)
    offsets = np.arange(len(values) + 1, dtype=np.int32) * 8
    return pa.StringArray.from_buffers(
        len(values), pa.py_buffer(offsets), pa.py_buffer(data))


def label_model(num_dense: int, cardinalities) -> dict:
    rng = np.random.default_rng(LABEL_MODEL_SEED)
    small = [j for j, v in enumerate(cardinalities) if v < SMALL_CARDINALITY]
    return {
        "dense_w": rng.normal(0.0, 0.5, num_dense),
        "small": small,
        "effects": {j: rng.normal(0.0, 0.7, cardinalities[j]) for j in small},
        "bias": -2.6,
    }


def raw_frame(seed: int, rows: int, num_dense: int, cardinalities,
              zipf_a: float):
    """(Arrow table, {column: numpy array of the raw integers})."""
    rng = np.random.default_rng([int(seed), 0xC417E0])
    model = label_model(num_dense, cardinalities)
    arrays, names, raw = [], [], {}
    logit = np.full(rows, model["bias"])
    for d in range(num_dense):
        counts = np.floor(rng.lognormal(1.0, 1.5, rows)).astype(np.int32)
        raw[f"i{d}"] = counts
        logit += model["dense_w"][d] * (np.log1p(counts) - 1.3)
        arrays.append(pa.array(counts))
        names.append(f"i{d}")
    for j, card in enumerate(cardinalities):
        ranks = bounded_zipf(rng, zipf_a, int(card), rows)
        if j in model["effects"]:
            logit += model["effects"][j][ranks]
        values = raw_values(ranks, j)
        raw[f"c{j}"] = values
        arrays.append(hex_strings(values))
        names.append(f"c{j}")
    label = (rng.random(rows) < 1.0 / (1.0 + np.exp(-logit))).astype(np.float32)
    raw["label"] = label
    arrays.append(pa.array(label))
    names.append("label")
    return pa.Table.from_arrays(arrays, names=names), raw
