"""Production meshes.  Functions, not module-level constants — importing this
module never touches jax device state."""
from __future__ import annotations

import jax


def _mk(shape, axes, devices=None):
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: 16x16 = 256 chips (TPU v5e pod).
    Multi-pod: 2x16x16 = 512 chips; the leading "pod" axis doubles as the
    DS-FL federated-client axis (DESIGN.md §5)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_client_mesh(n_clients: int, devices=None):
    """("pod", "data", "model") mesh over ``devices`` (default: every real
    device), with the federated-client axis on "pod" when the device count
    divides (one client per chip on a 4-chip host, the 8-fake-device CI
    tier; collapses to (1, 1, n) on one device).  Passing ``devices`` lets
    one process build meshes over different device subsets side by side."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    pod = n_clients if n >= n_clients and n % n_clients == 0 else 1
    return _mk((pod, 1, n // pod), ("pod", "data", "model"), devices)


def make_smoke_mesh(*, multi_pod: bool = False):
    """Same axis names on however many real devices exist (CPU tests)."""
    n = len(jax.devices())
    shape = (1, 1, n) if multi_pod else (1, n)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)
