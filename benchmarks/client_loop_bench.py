"""Time one DS-FL round with the client loops under `lax.map` and under
`jax.vmap`, for the paper's small nets at several client counts.

    PYTHONPATH=src python benchmarks/client_loop_bench.py
        [--cases NAME,...] [--paths map,vmap] [--reps N] [--out FILE]

`client.over_clients` picks `lax.map` when one client's step holds a
contraction of at least `client.MAP_MIN_MACS` multiply-adds and `vmap`
otherwise; these timings set that threshold.  This script forces each at
the four client-training sites of `DSFLAlgorithm` (the prediction vmap is
unchanged) and times the jitted round: compile seconds, then the median
of ``--reps`` rounds after one warm-up round.  Inputs are random arrays
of each model's shapes (what the round costs does not depend on the
values), made on the device.  Prints one JSON line per case and path,
with ``auto`` naming the path `over_clients` itself takes at each client
loop, and appends them to ``--out``.  Meant for an accelerator; on the
CPU pass a small case such as ``--cases mlp-k4``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import time
from types import SimpleNamespace

import jax
import jax.numpy as jnp

from repro.core import algorithms as alg
from repro.core.algorithms import BatchCtx, DSFLAlgorithm
from repro.core.client import loop_path
from repro.core.protocol import DSFLConfig
from repro.models import smallnets as sn

# model: (apply, init, input shape, input kind, classes); case: (model, K,
# protocol: private items per client, |o_r|, batch, epochs)
PAPER = dict(n_per=400, open_batch=1000, batch=100, epochs=(5, 5))
SMALL = dict(n_per=200, open_batch=500, batch=50, epochs=(2, 2))


def _cnn28(k):
    return sn.init_mnist_cnn(k)


def _cnn16(k):
    # benchmarks/common.py's CNN, which the paper-table scripts run
    return sn.init_mnist_cnn(k, image_hw=16, widths=(8, 16), fc=32)


def _cnn16w(k):
    # the paper's widths on 16-pixel images: a step between the two above
    return sn.init_mnist_cnn(k, image_hw=16)


MODELS = {
    "cnn28": (sn.apply_mnist_cnn, _cnn28, (28, 28, 1), "image", 10),
    "cnn16": (sn.apply_mnist_cnn, _cnn16, (16, 16, 1), "image", 10),
    "cnn16w": (sn.apply_mnist_cnn, _cnn16w, (16, 16, 1), "image", 10),
    "fmnist": (sn.apply_fmnist_cnn, sn.init_fmnist_cnn, (28, 28, 1), "image",
               10),
    "mlp": (sn.apply_tiny_mlp, sn.init_tiny_mlp, (16, 16, 1), "image", 10),
    "reuters": (sn.apply_reuters_dnn, sn.init_reuters_dnn, (10_000,), "bow",
                46),
    "lstm": (sn.apply_imdb_lstm, sn.init_imdb_lstm, (200,), "tokens", 2),
}
CASES = {
    "cnn28-k4": ("cnn28", 4, PAPER),
    "cnn28-k10": ("cnn28", 10, PAPER),
    "cnn28-k30": ("cnn28", 30, PAPER),
    "cnn16-k4": ("cnn16", 4, SMALL),
    "cnn16-k10": ("cnn16", 10, SMALL),
    "cnn16w-k10": ("cnn16w", 10, SMALL),
    "fmnist-k10": ("fmnist", 10, PAPER),
    "mlp-k4": ("mlp", 4, SMALL),
    "mlp-k100": ("mlp", 100, PAPER),
    "reuters-k10": ("reuters", 10, PAPER),
    "reuters-k100": ("reuters", 100, PAPER),
    "lstm-k10": ("lstm", 10, PAPER),
}
PATHS = {
    "map": lambda fn, *a: jax.lax.map(lambda x: fn(*x), a),
    "vmap": lambda fn, *a: jax.vmap(fn)(*a),
}


def make_case(name: str, seed: int = 0):
    """(algo, state, ctx, rng, client-steps a round) of one case."""
    model, K, p = CASES[name]
    apply_fn, init_fn, shape, kind, C = MODELS[model]
    eu, ed = p["epochs"]
    hp = DSFLConfig(local_epochs=eu, distill_epochs=ed,
                    batch_size=p["batch"], open_batch=p["open_batch"],
                    aggregation="era", temperature=0.1, seed=seed)
    algo = DSFLAlgorithm(apply_fn, hp)
    kx, ky, ko, kw = jax.random.split(jax.random.PRNGKey(seed), 4)
    n, n_open = p["n_per"], 2 * p["open_batch"]

    def draw(key, lead):
        if kind == "tokens":
            return jax.random.randint(key, lead + shape, 0, 20_000)
        x = jax.random.uniform(key, lead + shape)
        return jnp.floor(3 * x) if kind == "bow" else x

    x = draw(kx, (K, n))
    y = jax.random.randint(ky, (K, n), 0, C)
    open_x = draw(ko, (n_open,))
    state = algo.init(kw, init_fn, SimpleNamespace(x_clients=x))
    ctx = BatchCtx(x=x, y=y, open_x=open_x,
                   o_idx=jnp.arange(p["open_batch"]))
    steps = K * (eu * n // p["batch"] + ed * p["open_batch"] // p["batch"])
    return algo, state, ctx, jax.random.PRNGKey(seed + 1), steps


def auto_path(algo, state, ctx, rng) -> list:
    """The path `over_clients` takes at each of the round's client loops."""
    taken = []

    def spy(fn, *a):
        taken.append(loop_path(fn, *a))
        return PATHS["vmap"](fn, *a)

    saved, alg.over_clients = alg.over_clients, spy
    try:
        jax.eval_shape(algo.round, state, ctx, rng)
    finally:
        alg.over_clients = saved
    return taken


def time_case(name: str, path: str, reps: int) -> dict:
    algo, state, ctx, rng, steps = make_case(name)
    saved = alg.over_clients
    alg.over_clients = PATHS[path]
    try:
        t0 = time.perf_counter()
        lowered = jax.jit(lambda s, c, r: algo.round(s, c, r)).lower(
            state, ctx, rng)
        fn = lowered.compile()
        compile_s = time.perf_counter() - t0
    finally:
        alg.over_clients = saved
    out = fn(state, ctx, rng)                       # warm-up
    jax.block_until_ready(out)
    times = []
    for i in range(reps):
        t0 = time.perf_counter()
        out = fn(out[0], ctx, jax.random.fold_in(rng, i))
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    round_s = statistics.median(times)
    return {"case": name, "path": path,
            "auto": auto_path(algo, state, ctx, rng),
            "device": jax.devices()[0].device_kind,
            "compile_s": compile_s, "round_s": round_s,
            "round_s_all": times, "client_steps": steps,
            "ms_per_client_step": 1e3 * round_s / steps}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cases", default=",".join(CASES))
    ap.add_argument("--paths", default="map,vmap")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    for name in args.cases.split(","):
        for path in args.paths.split(","):
            line = json.dumps(time_case(name, path, args.reps))
            print(line, flush=True)
            if args.out:
                os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                            exist_ok=True)
                with open(args.out, "a") as f:
                    f.write(line + "\n")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
