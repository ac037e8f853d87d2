"""Utility tests (parity: reference python/raydp/tests/test_spark_utils.py)."""

import os

import numpy as np
import pytest

from raydp_tpu.utils import (
    BLOCK_SIZE_BIT,
    divide_blocks,
    expand_block_selection,
    memory_size_string,
    normalize_weights,
    pack_index,
    parse_memory_size,
    unpack_index,
)


@pytest.mark.parametrize(
    "text,expected",
    [
        ("1024", 1024),
        ("1K", 1024),
        ("1KB", 1024),
        ("500M", 500 << 20),
        ("500 MB", 500 << 20),
        ("2g", 2 << 30),
        ("1.5G", int(1.5 * (1 << 30))),
        ("3T", 3 << 40),
        (2048, 2048),
    ],
)
def test_parse_memory_size(text, expected):
    assert parse_memory_size(text) == expected


def test_parse_memory_size_rejects_garbage():
    with pytest.raises(ValueError):
        parse_memory_size("lots")


def test_memory_size_string_roundtrip():
    assert parse_memory_size(memory_size_string(500 << 20)) == 500 << 20


def test_normalize_weights():
    assert normalize_weights([1, 3]) == [0.25, 0.75]
    with pytest.raises(ValueError):
        normalize_weights([0, 0])
    with pytest.raises(ValueError):
        normalize_weights([-1, 2])


def test_pack_unpack_index():
    packed = pack_index(5, 123)
    assert packed == (5 << BLOCK_SIZE_BIT) | 123
    assert unpack_index(packed) == (5, 123)


def test_divide_blocks_equalizes_samples():
    blocks = [10, 5, 8, 7, 12, 3]
    world_size = 4
    result = divide_blocks(blocks, world_size)
    assert set(result) == set(range(world_size))
    per_rank = [sum(take for _, take in result[r]) for r in range(world_size)]
    # every rank must see exactly ceil(45/4)=12 samples
    assert per_rank == [12] * world_size
    for rank in range(world_size):
        for block_index, take in result[rank]:
            assert 0 <= block_index < len(blocks)
            assert 1 <= take <= blocks[block_index]


def test_divide_blocks_shuffle_is_deterministic():
    blocks = [4, 4, 4, 4, 4, 4, 4, 4]
    a = divide_blocks(blocks, 2, shuffle=True, shuffle_seed=7)
    b = divide_blocks(blocks, 2, shuffle=True, shuffle_seed=7)
    c = divide_blocks(blocks, 2, shuffle=True, shuffle_seed=8)
    assert a == b
    assert a != c


def test_divide_blocks_not_enough_blocks():
    with pytest.raises(ValueError):
        divide_blocks([5], 2)


def test_expand_block_selection():
    blocks = [3, 2]
    selection = [(0, 3), (1, 2)]
    packed = expand_block_selection(selection, blocks)
    decoded = [unpack_index(int(p)) for p in packed]
    assert decoded == [(0, 0), (0, 1), (0, 2), (1, 0), (1, 1)]
    assert packed.dtype == np.int64
    with pytest.raises(ValueError):
        expand_block_selection([(1, 3)], blocks)


def test_memory_size_string_exact_or_bytes():
    for n in [(1 << 30) + 1024, (1 << 30) + 512, (1 << 30) + 1, 999]:
        assert parse_memory_size(memory_size_string(n)) == n
    assert memory_size_string(1 << 30) == "1GB"


def test_host_side_planes_import_without_jax():
    """A chip belongs to one process. ETL executors, the store, the actor
    runtime and the serving DRIVER (deployment + batcher) must be importable
    — and are imported, in their own processes — without jax ever loading:
    an executor with jax in it is one call away from contending with the
    trainer for the chip (chip_smoke.py's fit phase asserts it live)."""
    import subprocess
    import sys

    modules = [
        "raydp_tpu.etl.executor", "raydp_tpu.etl.tasks",
        "raydp_tpu.store.object_store", "raydp_tpu.cluster.worker",
        "raydp_tpu.exchange", "raydp_tpu.exchange.dataset",
        "raydp_tpu.serve", "raydp_tpu.serve.deployment",
        "raydp_tpu.serve.batcher",
    ]
    program = (
        "import importlib, sys\n"
        f"for name in {modules!r}:\n"
        "    importlib.import_module(name)\n"
        "    assert 'jax' not in sys.modules, name + ' imported jax'\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    done = subprocess.run(
        [sys.executable, "-c", program], cwd=repo, capture_output=True,
        text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]
