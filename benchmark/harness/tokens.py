"""Packed token sequences from a seed (numpy/Arrow, vectorised).

A bounded Zipf(``zipf_a``) over the ``vocab`` ids, through a seeded
permutation (rank -> id), and a seeded bigram tilt: with probability ``tilt``
a token is ``succ[previous token]`` (one seeded successor per id), otherwise a
fresh Zipf draw. A model can so lower its held-out loss by the unigram
frequencies and by the successor table, both of which the training rows and
the held-out rows share. Sequences are packed: ``seq_len + 1`` ids each (the
last is the last position's target), no document boundary inside.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa

TOKENS, SEQ_ID = "tokens", "seq_id"


def sequences(seed: int, rows: int, seq_len: int, vocab: int, zipf_a: float,
              tilt: float) -> np.ndarray:
    """int32 [rows, seq_len + 1]; the same seed gives the same rows."""
    rng = np.random.default_rng(int(seed))
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** zipf_a
    cdf = np.cumsum(weights / weights.sum())
    rank_to_id = rng.permutation(vocab).astype(np.int32)
    succ = rank_to_id[np.minimum(
        np.searchsorted(cdf, rng.random(vocab)), vocab - 1)]
    width = seq_len + 1
    fresh = rank_to_id[np.minimum(
        np.searchsorted(cdf, rng.random((rows, width))), vocab - 1)]
    follow = rng.random((rows, width)) < tilt
    follow[:, 0] = False
    out = fresh.copy()
    # a run of k followers resolves in k passes (runs are geometric: short);
    # the fixed point is the sequence in which every follower is its
    # predecessor's successor
    while True:
        step = np.where(follow[:, 1:], succ[out[:, :-1]], out[:, 1:])
        if np.array_equal(step, out[:, 1:]):
            break
        out[:, 1:] = step
    return out


def raw_frame(seed: int, rows: int, seq_len: int, vocab: int, zipf_a: float,
              tilt: float):
    """(Arrow table with a ``FixedSizeList<int32>[seq_len + 1]`` column
    ``tokens`` and an int64 ``seq_id``, the int32 matrix it was made of)."""
    ids = sequences(seed, rows, seq_len, vocab, zipf_a, tilt)
    column = pa.FixedSizeListArray.from_arrays(
        pa.array(ids.ravel(), pa.int32()), seq_len + 1)
    table = pa.table({SEQ_ID: pa.array(np.arange(rows, dtype=np.int64)),
                      TOKENS: column})
    return table, ids
