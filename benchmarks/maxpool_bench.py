"""Time one MNIST CNN client step at batch 100 under each backward of the
2x2 max pool.

    PYTHONPATH=src python benchmarks/maxpool_bench.py
        [--variants NAME,...] [--clients K] [--reps N] [--out FILE]

The step is the DS-FL round's: `client.local_distill` (|o_r| = 1000 open
images, 5 epochs, 50 steps) and `client.local_update` (400 private
images, 5 epochs, 20 steps) of `smallnets.apply_mnist_cnn` under plain
SGD, over ``--clients`` clients one after another (`jax.lax.map`, as
`client.over_clients` runs the paper's CNN).  Each variant replaces
`smallnets.maxpool2` while the loops are traced:

- ``xla``: ``reduce_window`` differentiated by JAX, whose gradient XLA
  lowers to ``select_and_scatter`` (the pool before `maxpool2` had its
  own backward);
- ``stack-x`` / ``stack-xm``: a first-maximum mask per stride-2 phase of
  the input (``p == m`` and not taken by an earlier phase), the four
  phase gradients stacked back to full size; residual the input, or the
  input and the pooled max;
- ``pick-x`` / ``pick-idx``: `smallnets.maxpool2_grad`, an int8 index of
  the picked element per window and one select at full size; residual
  the input (`smallnets.maxpool2` as it is), or the index itself;
- ``pick-fwd``: as ``pick-idx``, with the forward's max taken by the same
  row-major walk that finds the index, in place of ``reduce_window``.

Inputs are random arrays of the step's shapes, made on the device.  Per
variant: compile seconds, then the median of ``--reps`` timed calls after
one warm-up call, and milliseconds per client-step of each loop.  Prints
one JSON line per variant naming the device, and appends it to ``--out``.
Meant for an accelerator; on the CPU pass ``--clients 1 --reps 1``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time

import jax
import jax.numpy as jnp

from repro.core.client import LocalSpec, local_distill, local_update
from repro.models import smallnets as sn
from repro.optim import optimizers as opt_lib

N_OPEN, N_PRIVATE, BATCH, EPOCHS = 1000, 400, 100, 5


def _phases(x):
    win = sn._windows(x)
    return [win[:, :, i, :, j, :] for i in (0, 1) for j in (0, 1)]


def _stack_grad(x, m, g):
    n, hh, ww, c = x.shape
    h, w = hh // 2, ww // 2
    taken, parts = None, []
    for p in _phases(x):
        hit = p == m
        sel = hit if taken is None else hit & ~taken
        taken = hit if taken is None else taken | hit
        parts.append(jnp.where(sel, g, jnp.zeros_like(g)))
    rows = [jnp.stack(parts[2 * i:2 * i + 2], axis=3) for i in (0, 1)]
    dx = jnp.stack(rows, axis=2).reshape(n, 2 * h, 2 * w, c)
    return jnp.pad(dx, ((0, 0), (0, hh - 2 * h), (0, ww - 2 * w), (0, 0)))


def _unpooled(g):
    # the input's shape: the step's pools see even sizes
    n, h, w, c = g.shape
    return n, 2 * h, 2 * w, c


def _custom(fwd, bwd):
    f = jax.custom_vjp(lambda x: fwd(x)[0])
    f.defvjp(fwd, bwd)
    return f


VARIANTS = {
    "xla": lambda: sn._max2,
    "stack-x": lambda: _custom(
        lambda x: (sn._max2(x), x),
        lambda x, g: (_stack_grad(x, sn._max2(x), g),)),
    "stack-xm": lambda: _custom(
        lambda x: (sn._max2(x), (x, sn._max2(x))),
        lambda r, g: (_stack_grad(r[0], r[1], g),)),
    "pick-x": lambda: sn.maxpool2,
    "pick-idx": lambda: _custom(
        lambda x: (sn._max2(x), sn._pool_pick(x)[1]),
        lambda pick, g: (sn._unpool(pick, g, _unpooled(g)),)),
    "pick-fwd": lambda: _custom(
        sn._pool_pick,
        lambda pick, g: (sn._unpool(pick, g, _unpooled(g)),)),
}


def _loops(clients: int):
    """(fn, args) of the two client loops over ``clients`` clients."""
    spec = LocalSpec(sn.apply_mnist_cnn, opt_lib.make("sgd", 0.1), EPOCHS,
                     BATCH)
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    w, s = jax.vmap(sn.init_mnist_cnn)(jax.random.split(keys[0], clients))
    o = jax.vmap(spec.opt.init)(w)
    x_open = jax.random.uniform(keys[1], (clients, N_OPEN, 28, 28, 1))
    teach = jax.nn.softmax(jax.random.normal(keys[2], (clients, N_OPEN, 10)))
    x_priv = jax.random.uniform(keys[3], (clients, N_PRIVATE, 28, 28, 1))
    y_priv = jax.random.randint(keys[4], (clients, N_PRIVATE), 0, 10)
    rngs = jax.random.split(keys[5], clients)

    def distill(w, s, o, x, t, r):
        return jax.lax.map(lambda a: local_distill(spec, *a),
                           (w, s, o, x, t, r))

    def update(w, s, o, x, y, r):
        return jax.lax.map(lambda a: local_update(spec, *a),
                           (w, s, o, x, y, r))

    return {"distill": (distill, (w, s, o, x_open, teach, rngs),
                        clients * EPOCHS * N_OPEN // BATCH),
            "update": (update, (w, s, o, x_priv, y_priv, rngs),
                       clients * EPOCHS * N_PRIVATE // BATCH)}


def time_variant(name: str, clients: int, reps: int) -> dict:
    out = {"variant": name, "device": jax.devices()[0].device_kind,
           "clients": clients}
    saved = sn.maxpool2
    for loop, (fn, args, steps) in _loops(clients).items():
        sn.maxpool2 = VARIANTS[name]()
        try:
            t0 = time.perf_counter()
            lowered = jax.jit(fn).lower(*args)
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
        finally:
            sn.maxpool2 = saved
        text = lowered.as_text()
        jax.block_until_ready(compiled(*args))                # warm-up
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(compiled(*args))
            times.append(time.perf_counter() - t0)
        med = statistics.median(times)
        out[loop] = {"compile_s": compile_s, "call_s": med,
                     "call_s_all": times, "client_steps": steps,
                     "ms_per_client_step": 1e3 * med / steps,
                     "select_and_scatter": "select_and_scatter" in text}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--variants", default=",".join(VARIANTS))
    ap.add_argument("--clients", type=int, default=10)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    for name in args.variants.split(","):
        line = json.dumps(time_variant(name, args.clients, args.reps))
        print(line, flush=True)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
