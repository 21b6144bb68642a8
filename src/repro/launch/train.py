"""Training driver.

Three modes, the federated ones running through the unified
`FedAlgorithm`/`FedEngine` API (`core.llm_algorithms`):
  * ``--mode dsfl``   - the paper's protocol at LLM scale: K simulated clients
    (vmapped; on the multi-pod mesh the client axis shards over pods), logit
    exchange on a shared open batch, ERA aggregation, hybrid CE+KD local steps.
  * ``--mode fedavg`` - Benchmark 1 at LLM scale: local SGD + parameter mean
    (the all-reduce whose bytes the paper's claim is measured against).
  * ``--mode local``  - plain LM pretraining (the "1. Update" benchmark).

The engine jits the round with mesh-aware ``in_shardings`` (client axis on
"pod" when the device count allows), donates the round state, measures the
exchange bytes on the encoded wire payload, and msgpack-checkpoints state +
round counter + history (``--ckpt``; a later run resumes the RNG stream).

``--participation``/``--straggler`` route the federated modes through the
`repro.sim` event simulator: a lognormal mobile fleet, uniform-K sampling,
and a virtual clock charged from the measured wire bytes — per-round output
then reports virtual wallclock and the participating cohort.

``--chunk-rounds k`` folds k rounds into one compiled ``lax.scan``
(`FedEngine.run(chunk_rounds=k)`) — bitwise identical to the per-round
loop, minus its per-round dispatch overhead.  Under ``--participation``/
``--straggler`` this is the *fused sim path*: the sync scheduler plans the
whole chunk's participation a priori and the (k, K) mask/stale plan rides
through the scan as per-step ctx inputs.

``--overlap`` software-pipelines the fused chunk: round r+1's logit
exchange (the cross-pod all-gather) is issued before round r's local
compute retires, so the wire hides behind compute.  Bitwise identical to
the sequential schedule — same ops, same order, split at the wire
boundary.  Pair it with ``--platform-preset overlap`` (or
``overlap-cpu8`` on CPU), which turns on XLA's latency-hiding scheduler
and async-collective lowering so the compiler actually exploits the slack
the schedule exposes.

On this CPU container use ``--smoke`` (reduced config).  Example:

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \
      --mode dsfl --clients 2 --steps 20 --chunk-rounds 5 --overlap \
      --platform-preset overlap-cpu8 [--participation 0.5 --straggler 30]
"""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, list_archs
from ..core import wire
from ..core.comm import fmt_bytes
from ..core.engine import FedEngine
from ..core.llm_algorithms import (LLMDSFLAlgorithm, LLMFedAvgAlgorithm,
                                   LLMFedAvgHP)
from ..core.llm_dsfl import LLMDsflHP, sgd_train_step
from ..data.pipeline import build_lm_task, lm_open_batch
from ..models.api import model_init
from ..models.base import ModelConfig, param_count
from ..models.shardctx import axis_ctx
from ..checkpoint import save_pytree
from ..obs import cli as obs_cli
from . import platform
from .mesh import make_client_mesh


def extra_inputs(cfg, batch, key):
    out = {}
    if cfg.arch_type == "vlm":
        out["patches"] = jax.random.normal(
            key, (batch, cfg.n_patches, cfg.d_model), jnp.float32
        ).astype(cfg.cdtype)
    if cfg.arch_type == "audio":
        out["frames"] = jax.random.normal(
            key, (batch, cfg.n_audio_frames, cfg.d_model), jnp.float32
        ).astype(cfg.cdtype)
    return out


def make_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b", choices=list_archs())
    ap.add_argument("--mode", default="dsfl",
                    choices=["dsfl", "fedavg", "local"])
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--clients", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--gamma", type=float, default=1.0)
    ap.add_argument("--aggregation", default="era", choices=["era", "sa"])
    ap.add_argument("--topk", type=int, default=None)
    ap.add_argument("--participation", type=float, default=1.0,
                    help="fraction of clients sampled per round (<1 runs the "
                         "round through the repro.sim event simulator)")
    ap.add_argument("--straggler", type=float, default=None,
                    help="virtual-seconds round deadline; late clients are "
                         "dropped (or admitted late with --straggler-policy)")
    ap.add_argument("--straggler-policy", default="drop",
                    choices=["drop", "admit"])
    ap.add_argument("--chunk-rounds", type=int, default=1,
                    help="rounds fused per compiled lax.scan chunk (bitwise "
                         "identical to the per-round loop); with "
                         "--participation/--straggler this runs the fused "
                         "sim path (sync participation planned per chunk)")
    ap.add_argument("--overlap", action="store_true",
                    help="software-pipeline the fused chunk: issue round "
                         "r+1's logit exchange before round r's compute "
                         "retires (bitwise identical to the sequential "
                         "schedule; needs --chunk-rounds >= 2)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--seed", type=int, default=0)
    platform.add_args(ap)
    obs_cli.add_args(ap)
    return ap


def main(argv=None):
    args = make_parser().parse_args(argv)
    # apply the XLA preset BEFORE anything touches the backend (obs session
    # provenance included) — XLA_FLAGS are read once at backend init
    platform.from_args(args)
    platform.enable_compile_cache()
    with obs_cli.session(args):
        run(args)


def build_federated(cfg: ModelConfig, args, mesh=None):
    """The objects a federated run (``--mode dsfl|fedavg``) drives, for any
    `ModelConfig`: ``(engine, initial state, task)``.  The engine jits the
    round on ``mesh`` (default `make_client_mesh(args.clients)`: the client
    axis on "pod" when the devices allow) and donates the round state."""
    K = args.clients
    task = build_lm_task(args.seed, K, args.batch, args.seq, cfg.vocab,
                         extras_fn=lambda b, k: extra_inputs(cfg, b, k))
    if args.mode == "dsfl":
        hp = LLMDsflHP(lr=args.lr, gamma=args.gamma,
                       aggregation=args.aggregation, topk=args.topk,
                       rounds=args.steps, seed=args.seed,
                       open_batch=args.batch)
        algo = LLMDSFLAlgorithm(cfg, hp)
        # the wire leg: top-k (value, index) pairs when sparsified, else
        # half-precision logits (probs travel as bf16 — 2 bytes each)
        codec = (wire.TopKCodec(k=args.topk, n_classes=cfg.vocab)
                 if args.topk else wire.FP16Codec())
    else:
        algo = LLMFedAvgAlgorithm(cfg, LLMFedAvgHP(
            lr=args.lr, rounds=args.steps, seed=args.seed))
        codec = wire.DenseF32Codec()
    eng = FedEngine(algo, codec=codec,
                    mesh=make_client_mesh(K) if mesh is None else mesh,
                    donate_state=True)
    # a jitted init: each leaf's f32 draw fuses into its bf16 cast instead
    # of materializing whole on the device
    state = eng.init(jax.jit(functools.partial(model_init, cfg)), task,
                     rng=jax.random.PRNGKey(args.seed))
    return eng, state, task


def run(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    key = jax.random.PRNGKey(args.seed)
    K = args.clients

    print(f"arch={cfg.name} ({cfg.arch_type}) layers={cfg.n_layers} "
          f"d={cfg.d_model} vocab={cfg.vocab}")
    if args.mode in ("dsfl", "fedavg"):
        eng, state, task = build_federated(cfg, args)
        mesh = eng.mesh
        one = jax.tree.map(lambda a: a[0], state.clients.params)
        print(f"params/client: {param_count(one):,}")
        # measured per-round exchange bytes (eval_shape: no compute), the
        # LLM-scale analogue of the paper's Table 1/2 upload accounting
        ex_bytes = eng.measured_round_bytes(state, task)
        fedavg_bytes = wire.nbytes(one) * (K + 1)
        print(f"exchange/round: {fmt_bytes(ex_bytes)} "
              f"(FedAvg parameter exchange would be "
              f"{fmt_bytes(fedavg_bytes)})")
        simulate = args.participation < 1.0 or args.straggler is not None
        if simulate and args.overlap:
            print("note: --overlap applies to the direct engine path; the "
                  "sim-routed rounds keep the sequential schedule")
        if simulate:
            # event-driven fleet: lognormal mobile links, uniform-K
            # participation, optional straggler deadline — the round runs
            # through the same engine, masked via BatchCtx.mask/stale
            from ..sim import ClientPopulation, SimRunner, SyncScheduler
            pop = ClientPopulation.lognormal(args.seed, K)
            runner = SimRunner(eng, SyncScheduler(
                pop, fraction=args.participation, deadline=args.straggler,
                straggler=args.straggler_policy), seed=args.seed)
        with axis_ctx(mesh, batch_axes=("data",)):
            done = 0
            while done < args.steps:
                k = max(1, min(args.chunk_rounds, args.steps - done))
                t0 = time.time()
                if simulate:
                    state = runner.run(state, task, rounds=k,
                                       chunk_rounds=k)
                    dt = (time.time() - t0) / k
                    for rec in runner.history.records[-k:]:
                        print(f"round {rec['round']-1:3d}  "
                              f"loss {rec['loss']:.4f}  "
                              f"vt {rec['t_cum']:8.1f}s  "
                              f"{rec['participants']}/{K} clients  "
                              f"{dt:.2f}s/round", flush=True)
                else:
                    state = eng.run(state, task, rounds=k, chunk_rounds=k,
                                    overlap=args.overlap)
                    dt = (time.time() - t0) / k
                    for rec in eng.history[-k:]:
                        print(f"round {rec['round']-1:3d}  "
                              f"loss {rec['loss']:.4f}  "
                              f"{dt:.2f}s/round", flush=True)
                done += k
        if args.ckpt:
            if simulate:
                runner.save_state(args.ckpt, state)   # + .sim.json sidecar
            else:
                eng.save_state(args.ckpt, state)
            print("saved", args.ckpt)
    else:
        params = model_init(cfg, key)
        print(f"params: {param_count(params):,}")
        kd, ke = jax.random.split(jax.random.fold_in(key, 1))
        batch = lm_open_batch(kd, args.batch, args.seq, cfg.vocab)
        batch.update(extra_inputs(cfg, args.batch, ke))
        step = jax.jit(lambda p, b: sgd_train_step(cfg, p, b, args.lr))
        for i in range(args.steps):
            t0 = time.time()
            params, loss = step(params, batch)
            loss.block_until_ready()
            print(f"step {i:3d}  loss {float(loss):.4f}  "
                  f"{time.time()-t0:.2f}s", flush=True)
        if args.ckpt:
            save_pytree(args.ckpt, params)
            print("saved", args.ckpt)


if __name__ == "__main__":
    main()
