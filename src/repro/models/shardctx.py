"""Activation-sharding context.

Model code calls ``constrain(x, "batch", None, "model")`` at layer
boundaries; outside a launch context this is a no-op, inside it becomes
``with_sharding_constraint`` with the launcher's axis mapping.  Explicit
activation constraints stop GSPMD from "solving" FSDP weight shardings by
all-reducing activation-sized partial sums (observed: a 40 GB logits
all-reduce on qwen1.5-4b before constraints were added — see §Perf).
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import PartitionSpec as P

_tls = threading.local()


def _state():
    if not hasattr(_tls, "ctx"):
        _tls.ctx = None
    return _tls.ctx


@contextlib.contextmanager
def axis_ctx(mesh, batch_axes=("data",), model_axis="model"):
    """Launcher context: axis names + sizes for divisibility guards."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    prev = _state()
    _tls.ctx = {
        "mesh": mesh,
        "batch": tuple(batch_axes),
        "batch_size": 1,
        "model": model_axis,
        "model_size": sizes.get(model_axis, 1),
    }
    for a in batch_axes:
        _tls.ctx["batch_size"] *= sizes.get(a, 1)
    try:
        yield
    finally:
        _tls.ctx = prev


def constrain(x: jax.Array, *dims):
    """dims: "batch" | "model" | None per array axis.  Divisibility-guarded;
    no-op without an active context."""
    ctx = _state()
    if ctx is None:
        return x
    spec = []
    for d, size in zip(dims, x.shape):
        if d == "batch" and size % ctx["batch_size"] == 0 and ctx["batch_size"] > 1:
            spec.append(ctx["batch"] if len(ctx["batch"]) > 1 else ctx["batch"][0])
        elif d == "model" and size % ctx["model_size"] == 0 and ctx["model_size"] > 1:
            spec.append(ctx["model"])
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(ctx["mesh"], P(*spec)))


def current_mesh():
    """The launcher context's mesh, or None outside `axis_ctx`."""
    ctx = _state()
    return None if ctx is None else ctx["mesh"]
