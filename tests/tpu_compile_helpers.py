"""What the ``test_tpu_compile_*`` files share: they compile the main path's
kernels and programs at real widths for a DESCRIBED TPU v5e (no chip here: the
TPU's compiler is installed, on-chip-measurement guide §2) and read what
interpret mode cannot show: VMEM budgets, tile alignment, what a program
holds, how many instructions it is. One file a cell family, so that no one
file is the suite's critical path; a new cell's pins are a new file. The
topology is described inside a fixture, never at import, and a test file
imports the fixtures it uses by name (nothing here is ``autouse``). Several
workers each describe the topology: the suite's command sets
``ALLOW_MULTIPLE_LIBTPU_LOAD=1`` for that; without it every file but the first
to load the TPU's library skips."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # noqa: BLE001 - no compiler here: nothing to test
        pytest.skip(f"no v5e:2x2 topology can be described here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def no_compile_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep it out
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture()
def kernels_compile(monkeypatch):
    # what the TPU backend would say of itself (ops/backend.py): a kernel
    # whose ``interpret`` is left to the rule compiles and is not interpreted
    # (one chip of it: with several devices and no mesh a Mosaic call that
    # XLA would have to partition falls back, ops/interaction.py)
    from raydp_tpu.ops import backend

    monkeypatch.setattr(backend, "on_tpu", lambda: True)
    monkeypatch.setattr(jax, "device_count", lambda *_: 1)


@pytest.fixture()
def mosaic_grids(monkeypatch):
    """[(kernel's name, its grid)] of every Mosaic call lowered while the
    fixture lives, read from the module Mosaic is handed
    (``iteration_bounds``): what the chip steps over, not what the caller
    meant to ask for."""
    from jax._src.pallas.mosaic import pallas_call_registration as registration

    mosaic = getattr(registration, "mosaic", None)
    if not hasattr(mosaic, "lower_module_to_custom_call"):
        pytest.skip("this jax hands Mosaic its module some other way")
    lower, lowered = mosaic.lower_module_to_custom_call, []

    def record(*args, module, kernel_name, **kwargs):
        bounds = re.search(r"iteration_bounds = array<i64: ([\d, ]+)>",
                           str(module))
        lowered.append((kernel_name, tuple(
            int(n) for n in bounds.group(1).split(",")) if bounds else ()))
        return lower(*args, module=module, kernel_name=kernel_name, **kwargs)

    monkeypatch.setattr(mosaic, "lower_module_to_custom_call", record)
    return lowered


def instructions(text):
    """The instructions of a compiled program's text: what a change that
    leaves a model's options as they were must not move."""
    return len(re.findall(r"^\s+(?:ROOT )?%[\w.\-]+ = ", text, re.M))


# the two-call pass's second call (PR 43: gone from the cells' programs;
# ``flash_attention_bwd_dq_dkv`` does not match)
BWD_DKV = r"%[\w.\-]*flash_attention_(?:window_)?bwd_dkv"
FLASH_FWD = r"%[\w.\-]*flash_attention_fwd[\w.\-]* = "


def calls(text, name):
    """How many instructions of ``text`` are the Mosaic call ``name``: the
    instruction's own name, %jvp_<name>_.1, %transpose_jvp_<name>__.1."""
    return len(re.findall(rf"%[\w.\-]*{name}[\w.\-]* = ", text))


def on_chip(tree, one_chip):
    """The shapes of ``tree`` as arguments that live on the described chip."""
    return jax.tree.map(lambda leaf: jax.ShapeDtypeStruct(
        leaf.shape, leaf.dtype, sharding=one_chip), tree)


def loss_products(text, scope):
    """The matrix products (the TPU compiler's ``convolution``s) under the
    loss's named scope: logits, the gradient back to the state, the head's
    gradient; a fourth would be a chunk's logits rebuilt (PR 32)."""
    return len([line for line in text.splitlines()
                if " convolution(" in line and scope in line])


def epoch_program(module, tx, steps, batch, tokens, one_chip):
    """An LM cell's epoch program as the resident scan runner compiles it:
    ``steps`` steps of ``batch`` x ``tokens`` tokens gathered from the
    resident rows by the epoch's permutation and scanned, the model's own
    loss, parameters and optimizer state donated, for the described chip.
    ``(params, compiled, held)``: the parameters' shapes, the program, and
    the bytes it holds (arguments + outputs - aliased + temporaries)."""
    from raydp_tpu.estimator.jax_estimator import (
        MODEL_LOSS, _scan_over_batches, make_train_step)

    place = functools.partial(on_chip, one_chip=one_chip)
    rows = place(jax.ShapeDtypeStruct((steps * batch, tokens + 1), jnp.int32))
    perm = place(jax.ShapeDtypeStruct((steps * batch,), jnp.int32))
    params = place(jax.eval_shape(
        lambda r, s: module.init(r, s, None, method="loss"),
        jax.random.PRNGKey(0), jax.ShapeDtypeStruct((1, tokens + 1), jnp.int32)))
    state = place(jax.eval_shape(tx.init, params))
    step = make_train_step(module, MODEL_LOSS, tx)

    def epoch(params, state, rows, perm):
        return _scan_over_batches(
            step, params, state,
            rows[perm].reshape(steps, batch, tokens + 1), None)

    compiled = jax.jit(epoch, donate_argnums=(0, 1)).lower(
        params, state, rows, perm).compile()
    memory = compiled.memory_analysis()
    held = (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes)
    return params, compiled, held


def cell_config(name):
    """``benchmark/configs/<name>.json``: a cell's configuration as published."""
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)
