"""Partitioner: the one place batch/state placement rules live.

The RecML-shaped abstraction (SNIPPETS.md [1]/[3]): ``shard_inputs`` puts a
host batch pytree onto the mesh, ``partition_step`` wraps the step function
under the same placement rules. Every feed path in the
repo (exchange ``device_put_batch``/``device_put_stacked``, the estimator's
scan/stream runners) routes through ONE ``DataParallelPartitioner`` so the
placement rules — and their sharp edges, catalogued below — cannot fork per
call site:

- **shard-direct** (default): inputs go through
  ``jax.make_array_from_process_local_data`` — each PROCESS contributes only
  its local rows and the runtime assembles the global array, so a multi-host
  feed never stages the global batch on one driver. Single-process this is
  semantically identical to a sharded ``device_put``; the toggle
  (``shard_direct=False``) keeps the legacy driver-staged ``device_put`` as
  the A/B arm (parity tests assert byte-identical results).
- **single-device meshes stay uncommitted**: dispatching a jitted step on a
  committed array (even SingleDeviceSharding) costs more than on an
  uncommitted one — 276 µs vs 192 µs per call for a tiny step on one TPU
  v5e (chip run, PR 21; what it costs a real fit is ROADMAP Queue 3 item
  8's to measure) — so the default device takes a plain ``jnp.asarray`` and
  only an explicit non-default device pins.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Sequence, Tuple

import numpy as np


def _mesh_device_count(mesh) -> int:
    try:
        return int(np.prod(list(mesh.shape.values())))
    except Exception:
        return 2  # unknown mesh type: assume multi-device


def _mesh_single_device(mesh):
    return np.asarray(mesh.devices).reshape(-1)[0]


class Partitioner:
    """Abstract partitioning logic for data and computation (RecML shape)."""

    def shard_inputs(self, inputs: Any) -> Any:
        """Shard a host batch pytree (leading dim = batch) onto devices."""
        raise NotImplementedError

    def shard_stacked(self, inputs: Any) -> Any:
        """Shard a STACKED [S, B, ...] segment pytree (scan dim leading,
        batch dim second) onto devices."""
        raise NotImplementedError

    def partition_step(self, fn: Callable, *, donate_argnums=()) -> Callable:
        """Jit a train/eval step under this partitioner's placement rules."""
        raise NotImplementedError


class NullPartitioner(Partitioner):
    """No-op placement: inputs pass through, steps get a plain jit."""

    def shard_inputs(self, inputs: Any) -> Any:
        return inputs

    def shard_stacked(self, inputs: Any) -> Any:
        return inputs

    def partition_step(self, fn: Callable, *, donate_argnums=()) -> Callable:
        from raydp_tpu.sanitize import checked_jit

        return checked_jit(fn, donate_argnums=donate_argnums)


class DataParallelPartitioner(Partitioner):
    """Batch dim sharded over ``axis``, params replicated (or ruled).

    ``shard_direct=True`` (default) feeds through
    ``make_array_from_process_local_data`` — the per-process upload path;
    ``False`` is the legacy driver-staged sharded ``device_put``. Both land
    byte-identical arrays; multi-host, only shard-direct avoids materializing
    the global batch per process.
    """

    def __init__(self, mesh, axis: str = "data", shard_direct: bool = True):
        self.mesh = mesh
        self.axis = axis
        self.shard_direct = bool(shard_direct)
        # resolved once — shard_inputs sits on the per-segment hot path
        self._single_device = None
        from raydp_tpu.obs import metrics

        self._direct_puts = metrics.counter("partitioner.shard_direct_puts")
        self._staged_puts = metrics.counter("partitioner.driver_staged_puts")

    # -- placement ------------------------------------------------------

    def _is_single_device(self) -> bool:
        if self._single_device is None:
            import jax

            self._single_device = (
                _mesh_device_count(self.mesh) <= 1 and jax.process_count() == 1
            )
        return self._single_device

    def _sharding(self, ndim: int, stacked: bool):
        from jax.sharding import NamedSharding, PartitionSpec

        if stacked:
            spec = PartitionSpec(None, self.axis, *([None] * (ndim - 2)))
        else:
            spec = PartitionSpec(self.axis, *([None] * (ndim - 1)))
        return NamedSharding(self.mesh, spec)

    def _put_leaf(self, x, stacked: bool):
        import jax

        if x is None:
            return None
        x = np.asarray(x)
        if self._is_single_device():
            import jax.numpy as jnp

            device = _mesh_single_device(self.mesh)
            if device == jax.devices()[0]:
                # default device: stay UNCOMMITTED — committed arrays cost
                # more per dispatch (module docstring)
                return jnp.asarray(x)
            return jax.device_put(x, device)  # explicit non-default pin
        sharding = self._sharding(max(1, x.ndim), stacked)
        if self.shard_direct or jax.process_count() > 1:
            # shard-direct: this process hands over only ITS rows; the
            # runtime assembles the global array (multi-process has no
            # driver-staged alternative — the global batch never exists in
            # any one process)
            self._direct_puts.inc()
            return jax.make_array_from_process_local_data(sharding, x)
        self._staged_puts.inc()
        return jax.device_put(x, sharding)

    def shard_inputs(self, inputs: Any) -> Any:
        import jax

        return jax.tree_util.tree_map(
            lambda x: self._put_leaf(x, stacked=False), inputs
        )

    def shard_stacked(self, inputs: Any) -> Any:
        import jax

        return jax.tree_util.tree_map(
            lambda x: self._put_leaf(x, stacked=True), inputs
        )

    # -- computation ----------------------------------------------------

    def partition_step(self, fn: Callable, *, donate_argnums=()) -> Callable:
        """Step jit under this partitioner's placement rules: donation-checked
        (``RAYDP_TPU_SANITIZE=donation`` verifies donated args against
        externally-owned host spans at dispatch) and mesh-scoped by the
        caller's ``with mesh`` context — the same ``checked_jit`` chain the
        estimator's ``partial_jit`` builds. The streaming runner jits its
        segment scan through here; the remaining estimator jit sites still
        call ``partial_jit`` directly (identical semantics)."""
        from raydp_tpu.sanitize import checked_jit

        return checked_jit(fn, donate_argnums=donate_argnums)
