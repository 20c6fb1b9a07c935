"""Mesh factories and the disaggregated engine's device assignment.

Factories are FUNCTIONS (not module-level constants) so importing this module
never touches jax device state. Single pod: (16, 16) = 256 chips
("data", "model"). Multi-pod: (2, 16, 16) = 512 chips ("pod", "data",
"model"). Activate a mesh with ``jax.set_mesh``.
"""
from __future__ import annotations

import math
from typing import Sequence

import jax


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              shrink: bool = False):
    """``jax.make_mesh`` with Auto axes. With ``shrink=True`` axis sizes are
    halved (largest-first) until the mesh fits the available device count —
    so single-host CPU runs still exercise the sharded code paths on a
    smaller mesh instead of failing the size assertion."""
    shape = list(shape)
    if shrink:
        n = jax.device_count()
        while math.prod(shape) > n:
            i = max(range(len(shape)), key=lambda j: shape[j])
            if shape[i] == 1:
                break
            shape[i] = max(1, shape[i] // 2)
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def handoff_devices(n_prefill: int, n_decode: int):
    """Assign local jax devices to disaggregated worker roles
    (``engine/workers.py``): prefill workers take the first half of the
    device list, decode workers the rest, round-robin within each role — so
    the prefill->decode KV handoff is a real cross-device ``jax.device_put``
    whenever the host has >= 2 devices. With a single device both lists are
    all-None, which the workers treat as "host-staged": pages ride through
    host memory (``jax.device_get`` then scatter), the same degradation the
    single-device engine's swap path uses."""
    devs = jax.devices()
    if len(devs) < 2:
        return [None] * n_prefill, [None] * n_decode
    split = max(1, min(len(devs) - 1, len(devs) // 2))
    pd, dd = devs[:split], devs[split:]
    return ([pd[i % len(pd)] for i in range(n_prefill)],
            [dd[i % len(dd)] for i in range(n_decode)])
