"""Production mesh construction.

A FUNCTION, not a module-level constant: importing this module never touches
jax device state (device count is locked at first jax init, and the 512
placeholder host devices are configured only by launch/dryrun.py).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes):
    # jax.make_mesh defaults to Explicit axes; constrain() and the
    # shard_map manual regions of the train steps need Auto ones
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips; the `pod` axis is
    pure data parallelism (one gradient all-reduce per update crosses it)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1, pod: Optional[int] = None):
    """CPU-sized mesh for tests/examples.

    ``pod`` adds a leading pod axis (multi-pod data parallelism), so the
    deferred-psum path across ("pod", "data") — one collective spanning
    both axes per optimizer update — is exercisable on host devices under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``. ``pod=None``
    (default) keeps the historical 2-axis ("data", "model") mesh."""
    if pod is None:
        return _auto_mesh((data, model), ("data", "model"))
    return _auto_mesh((pod, data, model), ("pod", "data", "model"))


def make_disagg_submeshes(
    prefill_pods: int = 1,
    decode_pods: int = 1,
    data: int = 1,
    model: int = 1,
    devices: Optional[Sequence] = None,
):
    """Carve one ``("pod", "data", "model")`` host grid into a disjoint
    (prefill, decode) submesh pair for disaggregated serving.

    The first ``(prefill_pods + decode_pods) * data * model`` devices are
    laid out as a pod-major grid and split along the pod axis: pods
    ``[0, prefill_pods)`` become the prefill submesh, the rest the decode
    submesh. Explicit device subsets — not two jax.make_mesh calls — so the
    pair is guaranteed disjoint and deterministic in device order. Each
    worker of :class:`~repro.serve.engine.DisaggregatedEngine` anchors its
    params/cache to its submesh's lead device
    (``mesh.devices.flat[0]``); KV page blocks stream between the two.

    Returns ``(prefill_mesh, decode_mesh)``, both with axes
    ``("pod", "data", "model")``.
    """
    if prefill_pods < 1 or decode_pods < 1:
        raise ValueError("prefill_pods and decode_pods must each be >= 1")
    devices = list(jax.devices()) if devices is None else list(devices)
    need = (prefill_pods + decode_pods) * data * model
    if len(devices) < need:
        raise ValueError(
            f"need {need} devices for a ({prefill_pods}+{decode_pods})x{data}x{model} "
            f"submesh pair, have {len(devices)} (run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} for host tests)"
        )
    grid = np.asarray(devices[:need]).reshape(prefill_pods + decode_pods, data, model)
    axes = ("pod", "data", "model")
    return Mesh(grid[:prefill_pods], axes), Mesh(grid[prefill_pods:], axes)


def make_data_mesh(width: int, devices: Optional[Sequence] = None) -> Mesh:
    """1-axis ("data",) mesh over the first ``width`` devices.

    The elastic data-parallel subsystem (repro.distributed) builds one of
    these per SEBS stage width: early narrow stages leave the remaining
    devices idle, later stages widen onto them. An explicit device subset —
    not jax.make_mesh — so every width nests as a prefix of the same device
    order (resharding between widths never permutes replicas)."""
    devices = list(jax.devices()) if devices is None else list(devices)
    if not 1 <= width <= len(devices):
        raise ValueError(f"width {width} not in [1, {len(devices)}]")
    return Mesh(np.asarray(devices[:width]), ("data",))
