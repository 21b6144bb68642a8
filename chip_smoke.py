"""Smoke run of the system's hot paths on a TPU, through the normal entry
points, at real widths, with random weights from a seed.

  python chip_smoke.py             # phases (a)-(c) on one chip
  python chip_smoke.py --chips 4   # phase (d) alone: the 4-chip client mesh

(a) The paper's DS-FL round: MNIST CNN at its published widths, K=100
    non-IID clients, |o_r|=1000, ERA through the fused Pallas kernel,
    rounds fused with ``chunk_rounds`` on `FedEngine`.
(b) qwen1.5-4b serving at full published widths and all 40 layers
    (bf16, ~7.1 GB) through `launch.serve.serve_continuous`; the cached
    path's logits against the full forward, in bf16 and again in f32.
(c) The fused KD loss kernel at qwen's vocabulary against its reference,
    then the LLM DS-FL round at qwen1.5-4b widths through the objects
    `launch.train` builds (`build_federated`), depth cut to fit two clients.
(d) With ``--chips 4``: phase (c)'s round with K=4 clients, one per chip,
    against the same seed's round with all four clients on one chip.

Every phase checks its outputs and raises on a failed check; nothing is
caught.  Earlier lines report the device, the cuts and each phase's
compile and steady seconds (both taken to ``block_until_ready``).  The
last line is one JSON object naming the device.  The script needs a TPU:
on any other platform it exits nonzero before running a phase.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.launch import platform  # noqa: E402  (needs the path above)

QWEN = "qwen1.5-4b"
# Phases (c)/(d) cut depth only: every width and the full 151,936-token
# vocabulary stay.  Two full-depth bf16 clients (~14 GB of weights) do not
# fit a 16 GB chip.  Each L below comes from compiling that phase's round
# for a described v5e chip and reading `compiled.memory_analysis()`
# (arguments + outputs - aliased + temporaries); the round's temporaries
# grow ~1.25 GB per layer at K=2 and hold an f32 embedding gradient per
# client (see PERF.md).
#   (c) K=2, open batch 4x128: L=4 -> 9.59 GB, L=6 -> 12.73 GB,
#       L=8 -> 15.90 GB.  L=6 is the deepest that leaves ~3 GB spare.
#   (d) K=4 on ONE chip (the reference run): L=2 at batch 4 -> 14.45 GB,
#       at batch 2 -> 12.85 GB; L=1 at batch 4 -> 13.16 GB.
L_TWO_CLIENTS, BATCH_TWO_CLIENTS = 6, 4
L_FOUR_CLIENTS, BATCH_FOUR_CLIENTS = 2, 2
LLM_ARGS = ["--mode", "dsfl", "--seq", "128", "--aggregation", "era"]
SEED = 0       # weights and data
# Phase (b): bounds on the relative RMS gap between the serving path's
# logits and the full forward.  On a v5e, seeds 0-3 read 1.844e-2 to
# 1.885e-2 in bf16 at 40 layers and 5.04e-7 to 5.24e-7 in f32 at
# F32_LAYERS layers (PERF.md).  The f32 bound also sits 13x below what a
# bf16-narrowed K/V cache or one stale ring slot gives on a small config.
BF16_GAP, F32_GAP, F32_LAYERS = 2.5e-2, 1e-4, 4

_compile_s = [0.0]     # seconds JAX spent compiling (or loading from cache)


def _on_duration(event: str, seconds: float, **_kw) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _compile_s[0] += seconds


def say(*parts) -> None:
    print(*parts, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")
    say(f"  ok: {what}")


class Phase:
    """Times one phase: compile seconds from JAX's backend-compile events
    (a persistent-cache hit counts its load time), wall seconds to the
    phase's last ``block_until_ready``."""

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        self.c0, self.t0 = _compile_s[0], time.perf_counter()
        say(f"== phase {self.name}")
        return self

    def __exit__(self, *exc):
        if exc[0] is None:
            say(f"   phase {self.name}: compile {_compile_s[0] - self.c0:.2f}s"
                f" total {time.perf_counter() - self.t0:.2f}s")
        return False


def timed(fn, *args, **kw):
    """(result, wall seconds, compile seconds) of one call, synced."""
    import jax
    c0, t0 = _compile_s[0], time.perf_counter()
    out = fn(*args, **kw)
    jax.block_until_ready(out)
    return out, time.perf_counter() - t0, _compile_s[0] - c0


def max_abs(a, b) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(a, np.float64)
                               - np.asarray(b, np.float64))))


# --------------------------------------------------------------- phase a ----
def phase_a(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core.aggregation import era, weighted_era
    from repro.core.algorithms import DSFLAlgorithm
    from repro.core.client import predict_probs
    from repro.core.engine import FedEngine, make_eval_fn
    from repro.core.protocol import DSFLConfig
    from repro.data.pipeline import build_image_task
    from repro.models.smallnets import apply_mnist_cnn, init_mnist_cnn

    K, chunk = 100, 2
    # the paper's MNIST split: 40k private images over K=100 clients (two
    # label shards each), 20k open, 10k test; 28x28 synthetic digits
    task = build_image_task(seed, K=K, n_private=40_000, n_open=20_000,
                            n_test=10_000, hw=28, distribution="non_iid")
    hp = DSFLConfig(aggregation="era", seed=seed)
    say(f"  K={K} clients, 400 private images each, |o_r|={hp.open_batch}, "
        f"{hp.local_epochs} local + {hp.distill_epochs} distill epochs, "
        f"T={hp.temperature}; {chunk} rounds per fused chunk")
    algo = DSFLAlgorithm(apply_mnist_cnn, hp, use_kernel=True)
    eng = FedEngine(algo, make_eval_fn(apply_mnist_cnn, task.x_test,
                                       task.y_test))
    state = eng.init(init_mnist_cnn, task)
    run = lambda s: eng.run(s, task, rounds=chunk, chunk_rounds=chunk,
                            log_every=chunk)
    state, t1, c1 = timed(run, state)
    state, t2, c2 = timed(run, state)
    say(f"  first chunk {t1:.2f}s (compile {c1:.2f}s); steady chunk "
        f"{t2:.2f}s = {t2 / chunk:.3f}s/round (compile {c2:.2f}s)")
    for rec in eng.history:
        say(f"  round {rec['round']}: " + " ".join(
            f"{k}={v:.4f}" for k, v in rec.items() if k != "round"))
    check(all(math.isfinite(v) for rec in eng.history for v in rec.values()),
          "every history value is finite")
    acc = eng.history[-1]["test_acc"]
    check(acc > 0.15, f"test accuracy {acc:.4f} > 0.15 (chance 0.10; "
          f"binomial sd at 10k test images is 0.003)")

    (hlo,) = eng.compiled_hlo(state, task)
    check("tpu_custom_call" in hlo,
          "the compiled round holds the Pallas ERA kernel (tpu_custom_call)")

    # kernel vs einsum teacher on the same uploads: this round's K client
    # predictions on 1000 open images
    xo = task.open_x[:hp.open_batch]
    probs = jax.jit(jax.vmap(lambda w, s: predict_probs(
        apply_mnist_cnn, w, s, xo)))(state.clients.params,
                                     state.clients.model_state)
    w = jnp.where(jnp.arange(K) % 3 == 0, 0.0,
                  jax.random.uniform(jax.random.PRNGKey(seed), (K,)) + 0.5)
    T = hp.temperature
    pairs = {
        "era": (jax.jit(lambda p: era(p, T, use_kernel=True)),
                jax.jit(lambda p: era(p, T))),
        "weighted_era": (
            jax.jit(lambda p: weighted_era(p, w, T, use_kernel=True)),
            jax.jit(lambda p: weighted_era(p, w, T))),
    }
    # Both sides average K=100 f32 probabilities in f32 and differ only in
    # summation order: <= K * 2^-24 ~ 6e-6 on the mean, x 1/T = 10 on the
    # logits, so <= ~6e-5 on a teacher probability.  A single-pass bf16
    # contraction (2^-9 relative) would move it by ~1e-2: 1e-4 separates.
    tol = 1e-4
    for name, (kern, ref) in pairs.items():
        d = max_abs(kern(probs), ref(probs))
        check(d <= tol, f"{name}: kernel vs einsum teacher max |diff| "
              f"{d:.3e} <= {tol:.0e}")
    del state, probs


# --------------------------------------------------------------- phase b ----
def phase_b(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import get_config
    from repro.launch.serve import serve_continuous, steady_ms_per_decode_step
    from repro.models.api import model_init
    from repro.models.base import param_count

    cfg = get_config(QWEN)
    params, t_init, _ = timed(jax.jit(lambda k: model_init(cfg, k)),
                              jax.random.PRNGKey(seed))
    n = param_count(params)
    say(f"  {cfg.name}: {cfg.n_layers} layers, d={cfg.d_model}, "
        f"ff={cfg.d_ff}, vocab={cfg.vocab}, {n:,} params "
        f"({n * 2 / 1e9:.2f} GB bf16), init {t_init:.2f}s")
    lens = (16, 18, 20, 24, 32, 36, 40, 48)     # buckets 16 and 32
    gen, decode_chunk = 8, 4
    seq_budget = max(lens) + gen
    rng = np.random.default_rng(seed)
    prompts = [tuple(int(t) for t in rng.integers(0, cfg.vocab, L))
               for L in lens]
    say(f"  {len(prompts)} requests, prompt lengths {lens}, {gen} new tokens "
        f"each, decode_chunk={decode_chunk}, batch_insert=True, "
        f"seq_budget={seq_budget}")
    serve = lambda: serve_continuous(cfg, params, prompts, gen, seq_budget,
                                     decode_chunk=decode_chunk,
                                     batch_insert=True)
    (resp, times), t1, c1 = timed(serve)
    (resp, times), t2, c2 = timed(serve)
    say(f"  first serve {t1:.2f}s (compile {c1:.2f}s); steady serve "
        f"{t2:.2f}s (compile/cache-load {c2:.2f}s), decode "
        f"{steady_ms_per_decode_step(times):.2f} ms/step over "
        f"{len(times)} dispatches")
    check(len(resp) == len(prompts)
          and all(len(r.tokens) == gen for r in resp),
          f"all {len(prompts)} requests finished with {gen} tokens")

    # prefill-then-decode logits vs the full forward, for the request with
    # a forced prompt tail (length 20 = bucket-16 prefix + 4 tail tokens)
    r = next(r for r in resp if r.prompt_len == 20)
    seq = np.asarray(prompts[lens.index(20)] + r.tokens, np.int32)
    gap = cached_vs_full(cfg, params, seq, 16, seq_budget)
    # bf16 (2^-8 = 3.9e-3 unit roundoff) on both paths, rounded at
    # different points (chunked attention vs ring-cache decode, batched vs
    # one-token matmuls) and compounded over 40 residual layers: a few
    # roundoffs, the same on every seed.  An fp8 path (2^-4) would be far
    # outside the bound; a cache-path defect need not be (the f32 witness
    # below is the one that sees it).
    check(gap["rel"] <= BF16_GAP, f"prefill+decode logits match model_logits:"
          f" relative RMS diff {gap['rel']:.3e} <= {BF16_GAP:.1e}")
    del params
    gc.collect()

    # The second witness: the same comparison in f32 with HIGHEST matmul
    # precision (4 layers), where the unit roundoff is 2^-24.  A gap made of
    # rounding shrinks with it by orders of magnitude; a cache-path defect
    # (a stale ring slot, K/V narrowed on the cache side only) does not.
    cfg32 = dataclasses.replace(cfg, n_layers=F32_LAYERS, dtype="float32")
    with jax.default_matmul_precision("highest"):
        params32 = jax.jit(lambda k: model_init(cfg32, k))(
            jax.random.PRNGKey(seed))
        gap32 = cached_vs_full(cfg32, params32, seq, 16, seq_budget)
    check(gap32["rel"] <= F32_GAP, f"f32, {F32_LAYERS} layers: prefill+decode "
          f"logits match model_logits: relative RMS diff {gap32['rel']:.3e} "
          f"<= {F32_GAP:.0e}")
    del params32


def cached_vs_full(cfg, params, seq, prefix: int, seq_budget: int) -> dict:
    """Logits of the serving path (prefill ``seq[:prefix]``, then one
    decode step per later token) against `model_logits`' full forward over
    ``seq``, at positions ``prefix-1 .. len(seq)-2``.  Compares logits, not
    tokens; prints and returns the gap."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.api import model_decode_step, model_logits, model_prefill
    pre = jax.jit(lambda p, t: model_prefill(cfg, p, {"tokens": t},
                                             seq_budget))
    dec = jax.jit(lambda p, c, t, pos: model_decode_step(cfg, p, c, t, pos))
    full = jax.jit(lambda p, t: model_logits(cfg, p, {"tokens": t},
                                             remat=False)[0])
    lg, cache = pre(params, jnp.asarray(seq[None, :prefix]))
    got = [lg[0]]
    for pos in range(prefix, len(seq) - 1):
        lg, cache = dec(params, cache, jnp.asarray(seq[pos:pos + 1]),
                        jnp.int32(pos))
        got.append(lg[0])
    got = np.stack([np.asarray(g, np.float32) for g in got])
    ref = np.asarray(full(params, jnp.asarray(seq[None]))[0, prefix - 1:-1],
                     np.float32)
    gap = {"rel": float(np.linalg.norm(got - ref) / np.linalg.norm(ref)),
           "max": max_abs(got, ref), "scale": float(np.abs(ref).max()),
           "agree": int(np.sum(got.argmax(-1) == ref.argmax(-1))),
           "n": len(ref)}
    say(f"  {cfg.n_layers} layers {cfg.dtype}, cached vs full forward over "
        f"{gap['n']} positions: relative RMS diff {gap['rel']:.3e}, max "
        f"|diff| {gap['max']:.3e} at logit scale {gap['scale']:.1f}; argmax "
        f"agrees at {gap['agree']}/{gap['n']}")
    return gap


# ------------------------------------------------------------ phases c, d ---
def _llm_cfg(n_layers: int):
    from repro.configs import get_config
    return dataclasses.replace(get_config(QWEN), n_layers=n_layers)


def _llm_args(clients: int, batch: int, rounds: int, seed: int):
    from repro.launch.train import make_parser
    return make_parser().parse_args(
        LLM_ARGS + ["--clients", str(clients), "--batch", str(batch),
                    "--steps", str(rounds), "--seed", str(seed)])


def _run_rounds(eng, state, task, rounds: int):
    """The CLI's round loop (`launch.train.run`), one round per call: the
    first call compiles, the rest are steady."""
    from repro.models.shardctx import axis_ctx
    with axis_ctx(eng.mesh, batch_axes=("data",)):
        state, t1, c1 = timed(eng.run, state, task, rounds=1)
        steady = []
        for _ in range(rounds - 1):
            state, t, _ = timed(eng.run, state, task, rounds=1)
            steady.append(t)
    say(f"  first round {t1:.2f}s (compile {c1:.2f}s); steady round "
        f"{min(steady):.2f}s")
    losses = [rec["loss"] for rec in eng.history]
    say(f"  losses {[round(x, 5) for x in losses]}")
    check(all(math.isfinite(x) for x in losses), "every round loss is finite")
    return state, losses


def check_distill_kernel(seed: int, n: int, vocab: int) -> None:
    """The fused KD loss (`kernels.ops.distill_loss`, the LLM distillation
    loss) compiled for this device at an (n, vocab) bf16 input, forward and
    gradient against `kernels.ref`."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    z = (jax.random.normal(k1, (n, vocab)) * 4).astype(jnp.bfloat16)
    t = jax.nn.softmax(jax.random.normal(k2, (n, vocab)), -1
                       ).astype(jnp.bfloat16)
    kern = jax.jit(jax.value_and_grad(ops.distill_loss))
    check("tpu_custom_call" in kern.lower(z, t).compile().as_text(),
          "the KD loss and its gradient run the Pallas kernel")
    loss, g = kern(z, t)
    want, ge = jax.jit(lambda z, t: (
        jnp.mean(ref.distill_loss_ref(z, t)),
        ref.distill_loss_grad_ref(z, t, jnp.float32(1.0))))(z, t)
    dl = abs(float(loss) - float(want)) / abs(float(want))
    g, ge = np.asarray(g, np.float32), np.asarray(ge, np.float32)
    dg = float(np.linalg.norm(g - ge) / np.linalg.norm(ge))
    say(f"  KD loss kernel at {n} x {vocab} (vocab padded to its 2048 "
        f"block): loss {float(loss):.6f} vs reference {float(want):.6f}")
    # Both read the same bf16 input and reduce in f32; they differ in
    # summation order (blocked online logsumexp vs one reduction): ~1e-6
    # relative on the loss.  The kernel rounds its f32 gradient to bf16
    # once, <= 2^-9 relative per element, so <= 2^-9 in relative RMS.
    check(dl <= 1e-5, f"KD loss: relative diff {dl:.3e} <= 1e-05")
    check(dg <= 2 ** -8, f"KD gradient: relative RMS diff {dg:.3e} <= "
          f"{2 ** -8:.2e} (bf16 output)")


def phase_c(seed: int) -> None:
    import jax
    from repro.launch.train import build_federated
    from repro.models.base import param_count

    cfg = _llm_cfg(L_TWO_CLIENTS)
    check_distill_kernel(seed, 1024, cfg.vocab)
    args = _llm_args(clients=2, batch=BATCH_TWO_CLIENTS, rounds=3,
                     seed=seed)
    say(f"  depth cut: {cfg.n_layers} of 40 layers: two full-depth bf16 "
        f"clients are ~14 GB of weights alone, and the v5e compile "
        f"rehearsal's memory_analysis puts this round at 12.73 GB of 16 "
        f"(L=8: 15.90 GB); widths and vocab {cfg.vocab} kept")
    (eng, state, task), t_init, _ = timed(build_federated, cfg, args)
    one = jax.tree.map(lambda a: a[0], state.clients.params)
    say(f"  K={args.clients} clients x {param_count(one):,} params, "
        f"open batch {args.batch} x {args.seq} tokens, dense ERA exchange, "
        f"mesh {dict(eng.mesh.shape)}, init {t_init:.2f}s")
    _run_rounds(eng, state, task, args.steps)


def _teacher(eng, state, task):
    """The ERA teacher of ``state``'s clients on the first open batch,
    computed under the engine's mesh (a cross-chip mean when the client
    axis is sharded)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.llm_dsfl import aggregate_teacher, dsfl_exchange
    from repro.models.shardctx import axis_ctx
    algo = eng.algo
    open_b = jax.tree.map(lambda a: a[:algo.hp.open_batch], task.open_x)
    fn = jax.jit(lambda p, ob: aggregate_teacher(
        dsfl_exchange(algo.cfg, p, ob, algo.hp)[0], algo.hp, None))
    with axis_ctx(eng.mesh, batch_axes=("data",)):
        t = fn(state.clients.params, open_b)
    return np.asarray(t.astype(jnp.float32))


def phase_d(seed: int) -> None:
    import jax
    import numpy as np
    from repro.launch.mesh import make_client_mesh
    from repro.launch.train import build_federated

    devs = jax.devices()
    check(len(devs) >= 4, f"{len(devs)} devices >= 4")
    K, rounds = 4, 3
    cfg = _llm_cfg(L_FOUR_CLIENTS)
    args = _llm_args(clients=K, batch=BATCH_FOUR_CLIENTS, rounds=rounds,
                     seed=seed)
    say(f"  depth cut: {cfg.n_layers} of 40 layers, open batch "
        f"{args.batch}x{args.seq}, so that one chip holds all {K} clients "
        f"of the reference run (compile rehearsal: 12.85 GB of 16); widths "
        f"and vocab {cfg.vocab} kept")
    out = {}
    for name, mesh_devs in (("1-chip", devs[:1]), ("4-chip", devs[:4])):
        say(f"  -- {name}")
        eng, state, task = build_federated(
            cfg, args, mesh=make_client_mesh(K, devices=mesh_devs))
        say(f"  mesh {dict(eng.mesh.shape)} over {len(mesh_devs)} device(s)")
        state, losses = _run_rounds(eng, state, task, rounds)
        out[name] = (losses, _teacher(eng, state, task))
        if name == "4-chip":
            owner = None
            for leaf in jax.tree.leaves(state.clients.params):
                by_client = {}
                for sh in leaf.addressable_shards:
                    c = sh.index[0]
                    if c.stop - c.start != 1:
                        raise AssertionError(f"a shard of {leaf.shape} holds "
                                             f"clients {c}")
                    by_client.setdefault(c.start, set()).add(sh.device)
                placed = {c: tuple(sorted(d.id for d in ds))
                          for c, ds in by_client.items()}
                owner = owner or placed
                if placed != owner:
                    raise AssertionError(f"placement differs: {placed}")
            check(sorted(owner) == list(range(K))
                  and len({d for ds in owner.values() for d in ds}) == K
                  and all(len(ds) == 1 for ds in owner.values()),
                  f"one client per device on every parameter leaf: "
                  f"client -> device {owner}")
            (hlo,) = eng.compiled_hlo(state, task)
            found = [c for c in ("all-reduce", "all-gather",
                                 "collective-permute", "all-to-all",
                                 "reduce-scatter") if c in hlo]
            check(bool(found), f"the compiled 4-chip round holds cross-chip "
                  f"collectives: {found}")
        del eng, state, task
        gc.collect()

    (l1, t1), (l4, t4) = out["1-chip"], out["4-chip"]
    # Same seed, same data, same math; the programs differ only in how
    # XLA tiles and fuses one client's work (K=4 batched on one chip vs
    # K=1 per chip), so bf16 roundings land differently: losses agree to a
    # few bf16 roundoffs (2^-8 each) and the teacher, whose ERA sharpening
    # scales probability differences by 1/T = 10, to a few percent.
    rel = max(abs(a - b) / abs(b) for a, b in zip(l4, l1))
    check(rel <= 1e-2, f"4-chip vs 1-chip round losses: max relative diff "
          f"{rel:.3e} <= 1e-2")
    dt_max, dt_mean = max_abs(t4, t1), float(np.mean(np.abs(t4 - t1)))
    check(dt_max <= 5e-2 and dt_mean <= 1e-4,
          f"4-chip vs 1-chip ERA teacher: max |diff| {dt_max:.3e} <= 5e-2, "
          f"mean |diff| {dt_mean:.3e} <= 1e-4")


# ------------------------------------------------------------------ main ----
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-chip client-mesh phase (d)")
    args = ap.parse_args(argv)

    cache = platform.enable_compile_cache()
    import jax
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind})",
              file=sys.stderr)
        return 2
    say(f"device: {devs[0].device_kind} x {len(devs)} ({devs[0].platform}); "
        f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    if args.chips == 4:
        with Phase("(d) LLM DS-FL round, 4 chips vs 1 chip"):
            phase_d(SEED)
    else:
        with Phase("(a) paper DS-FL round, MNIST CNN, K=100"):
            phase_a(SEED)
        with Phase(f"(b) {QWEN} serving, 40 layers"):
            phase_b(SEED)
        with Phase(f"(c) LLM DS-FL round at {QWEN} widths"):
            phase_c(SEED)
    say(f"total compile {_compile_s[0]:.2f}s, total "
        f"{time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
