"""Serving driver over `repro.serve`: continuous-batching greedy decode
with the ring-buffer KV cache / SSM state.  This is the substrate behind
the decode_32k / long_500k dry-run shapes; at smoke scale it runs
end-to-end on CPU.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-2.7b --smoke \
      --batch 4 --prompt-len 32 --gen 16

The default path drives `repro.serve.ServeEngine` (slot-based continuous
batching: requests with different prompt lengths join and leave the decode
batch without recompiling).  ``--decode-chunk d`` folds d decode steps
into one fused dispatch (one host sync per chunk) and ``--batch-insert``
admits same-bucket request groups through one compiled batched prefill —
both token-identical to the step-at-a-time defaults.  ``--lockstep`` runs
the pre-subsystem whole-batch baseline — one prefill, all requests
decoding in lockstep — kept because tests pin ServeEngine token-identical
to it.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs import get_config, list_archs
from ..models.api import (model_decode_step, model_init, model_prefill)
from ..obs import cli as obs_cli
from ..serve import AdmissionQueue, ServeEngine
from . import platform
from .train import extra_inputs


def serve(cfg, params, batch: dict, gen: int, seq_budget: int):
    """Lockstep greedy generation (whole batch prefilled and decoded
    together).  Returns (tokens (B, gen), per-step seconds); the first
    entry of the times list is the compile step — report on times[1:]."""
    B, S0 = batch["tokens"].shape
    prefill_j = jax.jit(lambda p, b: model_prefill(cfg, p, b, seq_budget))
    step_j = jax.jit(lambda p, c, t, pos: model_decode_step(cfg, p, c, t, pos))
    logits, cache = prefill_j(params, batch)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    out, times = [tok], []
    pos0 = S0 + (cfg.n_patches if cfg.arch_type == "vlm" else 0)
    for i in range(gen - 1):
        t0 = time.perf_counter()
        logits, cache = step_j(params, cache, tok, jnp.int32(pos0 + i))
        logits.block_until_ready()
        times.append(time.perf_counter() - t0)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out.append(tok)
    return jnp.stack(out, 1), times


def steady_ms_per_step(times) -> float:
    """Mean decode ms/step excluding the first (compile) step."""
    steady = times[1:] if len(times) > 1 else times
    return 1e3 * sum(steady) / max(len(steady), 1)


def serve_continuous(cfg, params, prompts, gen: int, seq_budget: int, *,
                     decode_chunk: int = 1, batch_insert: bool = False):
    """The same workload through the continuous-batching subsystem: each
    prompt is a request; slots = number of requests so everything is
    admitted immediately.  ``decode_chunk``/``batch_insert`` select the
    fused fast paths (token-identical to the defaults).  Returns
    (responses by id, list of (seconds, decode steps) per dispatch)."""
    engine = ServeEngine(cfg, params, slots=len(prompts),
                         seq_budget=seq_budget)
    queue = AdmissionQueue(buckets=engine.buckets)
    # one clock for the whole request lifecycle (arrival/admission/steps),
    # so the latency bookkeeping on Response is internally consistent
    t0 = time.perf_counter()
    for toks in prompts:
        queue.submit(toks, gen, now=time.perf_counter() - t0)
    if batch_insert:
        while True:
            reqs = queue.admit(time.perf_counter() - t0,
                               len(engine.free_slots()), group=True)
            if not reqs:
                break
            engine.insert_batch(reqs, time.perf_counter() - t0)
    else:
        for req in queue.admit(time.perf_counter() - t0,
                               len(engine.free_slots())):
            engine.insert(req, time.perf_counter() - t0)
    times = []
    while engine.n_active:
        before = engine.n_steps
        ts = time.perf_counter()
        engine.step(time.perf_counter() - t0, decode_chunk=decode_chunk)
        times.append((time.perf_counter() - ts, engine.n_steps - before))
    by_id = {r.id: r for r in engine.pop_completed()}
    return [by_id[i] for i in sorted(by_id)], times


def steady_ms_per_decode_step(timed_steps) -> float:
    """Mean decode ms per accounted step from ``serve_continuous`` timing
    pairs, excluding the first (compile) dispatch."""
    steady = timed_steps[1:] if len(timed_steps) > 1 else timed_steps
    n = sum(k for _, k in steady)
    return 1e3 * sum(dt for dt, _ in steady) / max(n, 1)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-2.7b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--lockstep", action="store_true",
                    help="pre-subsystem whole-batch baseline path")
    ap.add_argument("--decode-chunk", type=int, default=1,
                    help="fuse this many decode steps into one compiled "
                         "scan (one host sync per chunk; token-identical)")
    ap.add_argument("--batch-insert", action="store_true",
                    help="admit same-bucket request groups through one "
                         "compiled batched prefill (token-identical)")
    platform.add_args(ap)
    obs_cli.add_args(ap)
    args = ap.parse_args(argv)
    # preset before backend init: XLA_FLAGS are read once
    platform.from_args(args)
    platform.enable_compile_cache()
    with obs_cli.session(args):
        run(args)


def run(args):
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    key = jax.random.PRNGKey(args.seed)
    params = model_init(cfg, key)
    kt, ke = jax.random.split(key)
    tokens = jax.random.randint(
        kt, (args.batch, args.prompt_len), 0, cfg.vocab, jnp.int32)
    seq_budget = args.prompt_len + args.gen + \
        (cfg.n_patches if cfg.arch_type == "vlm" else 0)

    if args.lockstep or cfg.arch_type in ("vlm", "audio"):
        # modality archs need per-request frames/patches the slot engine
        # doesn't carry yet — they stay on the lockstep path
        batch = {"tokens": tokens}
        batch.update(extra_inputs(cfg, args.batch, ke))
        toks, times = serve(cfg, params, batch, args.gen, seq_budget)
        print(f"[lockstep] generated {toks.shape} tokens; "
              f"decode {steady_ms_per_step(times):.1f} ms/step")
        print(toks[0])
        return

    prompts = [tuple(int(t) for t in row) for row in jax.device_get(tokens)]
    responses, times = serve_continuous(
        cfg, params, prompts, args.gen, seq_budget,
        decode_chunk=args.decode_chunk, batch_insert=args.batch_insert)
    n_tok = sum(len(r.tokens) for r in responses)
    print(f"[continuous] {len(responses)} requests, {n_tok} tokens; "
          f"decode {steady_ms_per_decode_step(times):.1f} ms/step over "
          f"{len(times)} dispatches (chunk={args.decode_chunk}, "
          f"batch_insert={args.batch_insert}, "
          f"weights v{responses[0].weights_version})")
    print(jnp.asarray(responses[0].tokens))


if __name__ == "__main__":
    main()
