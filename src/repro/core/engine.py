"""`FedEngine` — one algorithm-agnostic federated trainer.

Generalizes the seed `protocol.DSFLEngine` to any `FedAlgorithm`: jits the
algorithm's round once, samples the shared open batch o_r (when the
algorithm uses one), runs test-set eval through ``algo.eval_params``,
accumulates a scalar history, measures wire bytes through a `wire.Codec`,
and checkpoints the full typed `RoundState` with the msgpack backend —
together with the round counter and history, so save/load/run resumes the
exact RNG stream without the caller hand-tracking ``start_round``.

``run(..., chunk_rounds=k)`` compiles k federated rounds into a single
``jax.lax.scan``: the per-round RNG chain, the open-batch draw and the
algorithm's round all live inside one jit, with per-round metrics stacked
on device and pulled to the host once per chunk — so the Python-loop
overhead (one dispatch + one ``float()`` sync per round) disappears from
the hot path.  The scanned path is **bitwise identical** to the default
per-round loop (same key stream, same history), pinned by
``tests/test_engine_scan.py`` across DSFL/FD/FedAvg.

For the pod-scale LLM algorithms, pass ``mesh=`` (and optionally
``donate_state=True``): the engine builds its jit with mesh-aware
``in_shardings`` from ``algo.shardings(mesh, state, ctx)`` — the
`launch.sharding` placement rules — and donates the round state's buffers.
Both compose with ``chunk_rounds``.

RNG discipline matches the seed engine exactly (``rng, rk, ri =
split(rng, 3)`` per round; o_r drawn from ``ri``; the round keyed by
``rk``) so `DSFLAlgorithm` under this engine is bit-for-bit identical to
the reference `DSFLEngine` — asserted by ``tests/test_engine.py``.
"""
from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import assert_tree_compatible, load_pytree, save_pytree
from ..obs import trace as obs
from .algorithms import BatchCtx, EMPTY, FedAlgorithm, RoundState
# re-exported so new-API callers need only this module (the implementation
# lives with the reference engine)
from .protocol import make_eval_fn  # noqa: F401
from .wire import Codec, DenseF32Codec, nbytes


def _leading_dim(tree) -> int:
    """First-axis size of a (possibly dict-of-arrays) batch pytree."""
    return jax.tree.leaves(tree)[0].shape[0]


@jax.jit
def _fast_forward_key(rng, n):
    """Advance the per-round key chain (``rng <- split(rng, 3)[0]``) past n
    completed rounds entirely on device.  ``n`` is traced, so one compiled
    loop serves every resume point — resuming at round 10k is a single
    dispatch, not 10k host-side ``jax.random.split`` calls — and the result
    is bitwise the key the host loop would produce (asserted by
    ``tests/test_engine_scan.py``)."""
    return jax.lax.fori_loop(
        0, n, lambda _, k: jax.random.split(k, 3)[0], rng)


@dataclass
class FedEngine:
    """Python-level orchestration around ``jax.jit(algo.round)``.

    ``eval_fn(params, model_state) -> dict`` is called on
    ``algo.eval_params(state)`` every ``log_every`` rounds; its scalars join
    the round metrics in ``history``.  Non-scalar round metrics (e.g. FD's
    (C, C) global logit) are kept out of the history but exposed on
    ``last_metrics``.  ``on_round(r, state) -> state`` runs un-jitted
    between rounds (attack injection, LR rescheduling, ...).

    ``mesh``: when set and the algorithm exposes ``shardings``, the round is
    jitted with mesh-aware ``in_shardings`` (built lazily from the first
    round's state/ctx).  ``donate_state=True`` donates the round-state
    buffers to the jit (halves peak params memory for the LLM algorithms).
    ``rounds_done`` counts completed rounds; it is checkpointed by
    ``save_state`` and restored by ``load_state`` so a resumed ``run``
    continues the per-round RNG chain automatically.

    ``on_chunk(rounds_done, state) -> None`` is a pure *observer* called
    whenever a freshly-computed state lands on the host: after every chunk
    on the scanned path, after every round on the loop path.  Unlike
    ``on_round``/``on_ctx`` it cannot rewrite the state, so it does NOT
    force the per-round loop — `repro.serve.swap` uses it to hot-swap a
    running server's weights at ``chunk_rounds`` boundaries while the
    training stream stays fully fused."""
    algo: FedAlgorithm
    eval_fn: Optional[Callable] = None
    codec: Codec = field(default_factory=DenseF32Codec)
    on_round: Optional[Callable] = None
    on_ctx: Optional[Callable] = None
    on_chunk: Optional[Callable] = None
    mesh: Optional[Any] = None
    donate_state: bool = False
    history: list = field(default_factory=list)
    last_metrics: dict = field(default_factory=dict)
    rounds_done: int = 0

    def __post_init__(self):
        self._round = None       # manual override slot (None = use the cache)
        self._round_cache = {}   # (state, ctx) treedef -> jitted round
        self._chunk_cache = {}   # scan signature -> jitted k-round driver

    def _build_round(self, state: RoundState, ctx: BatchCtx):
        kw = {}
        if self.donate_state:
            kw["donate_argnums"] = (0,)
        shard_fn = getattr(self.algo, "shardings", None)
        if self.mesh is not None and shard_fn is not None:
            state_sh, ctx_sh = shard_fn(self.mesh, state, ctx)
            kw["in_shardings"] = (state_sh, ctx_sh, None)
            # pin the output state to the same placement: round r+1 consumes
            # round r's output, so a free XLA choice here would hand the next
            # call an arg whose sharding mismatches in_shardings
            kw["out_shardings"] = (state_sh, None)
        return jax.jit(self.algo.round, **kw)

    def _get_round(self, state: RoundState, ctx: BatchCtx):
        """The jitted round for this (state, ctx) *structure*.  Keyed on the
        tree structure because ``on_ctx`` (or a sim plan) can flip
        ``ctx.mask``/``stale`` from EMPTY to arrays mid-run: a round (and its
        ``in_shardings``) built from the first round's treedef would then be
        handed a ctx it was never built for — the stale-cache landmine
        pinned by ``tests/test_engine_scan.py``."""
        if self._round is not None:
            return self._round
        key = jax.tree_util.tree_structure((state, ctx))
        fn = self._round_cache.get(key)
        if fn is None:
            fn = self._round_cache[key] = self._build_round(state, ctx)
        return fn

    def _build_chunk(self, k: int, n_open: int, n_r: int, state: RoundState,
                     ctx0: BatchCtx, plan, overlap: bool = False):
        """One jit folding k federated rounds into a ``jax.lax.scan``: the
        per-round key chain, the open-batch draw and the algorithm's round
        all run on device; metrics come back stacked over the chunk.
        ``plan`` (optional) is a dict of per-round BatchCtx overrides with a
        leading (k,) axis — e.g. a sim scheduler's participation mask —
        scanned through as per-step inputs.

        ``overlap=True`` builds the software-pipelined schedule instead:
        the algorithm's round splits into ``round_start`` (the wire leg —
        prediction + the cross-pod upload all-gather) and ``round_finish``
        (the compute leg), and the scan body finishes round r *then*
        issues round r+1's start — so r+1's exchange is already in flight
        while nothing after it in program order depends on it, and a
        latency-hiding scheduler (`launch.platform`'s ``overlap`` preset)
        can sink it under r+1's private-data update leg.  The carry
        double-buffers the in-flight exchange tensors.  Prologue (start
        round 0) + k-1 bodies + epilogue (finish round k-1) = exactly k
        starts and k finishes: same ops, same key chain (k ``split``s),
        same per-round inputs — **bitwise identical** to the sequential
        schedule (``round == finish ∘ start`` by construction; pinned by
        ``tests/test_overlap.py`` / ``tests/test_engine_scan.py``)."""
        algo = self.algo
        uses_open = algo.uses_open

        def draw(rng):
            """The engine's per-round RNG discipline, shared verbatim by
            both schedules: one 3-way split, o_r drawn from ``ri``."""
            rng, rk, ri = jax.random.split(rng, 3)
            o_idx = (jax.random.choice(ri, n_open, (n_r,), replace=False)
                     if uses_open else EMPTY)
            return rng, rk, o_idx

        def mk_ctx(ctx0, o_idx, step):
            ctx = ctx0
            if uses_open:
                ctx = dataclasses.replace(ctx, o_idx=o_idx)
            if step is not None:
                ctx = dataclasses.replace(ctx, **step)
            return ctx

        def chunk_fn(state, ctx0, rng, plan):
            def body(carry, step):
                state, rng = carry
                rng, rk, o_idx = draw(rng)
                state, m = algo.round(state, mk_ctx(ctx0, o_idx, step), rk)
                return (state, rng), m
            (state, rng), ms = jax.lax.scan(body, (state, rng), plan,
                                            length=k)
            return state, rng, ms

        def chunk_fn_pipelined(state, ctx0, rng, plan):
            # prologue: put round 0's exchange in flight
            rng, rk, o_idx = draw(rng)
            step0 = (None if plan is None
                     else jax.tree.map(lambda v: v[0], plan))
            inflight = algo.round_start(state, mk_ctx(ctx0, o_idx, step0),
                                        rk)

            def body(carry, step_next):
                state, rng, inflight, rk, o_idx, step = carry
                # finish round r with the buffers issued one body earlier...
                state, m = algo.round_finish(
                    state, mk_ctx(ctx0, o_idx, step), inflight, rk)
                # ...then issue round r+1's exchange against the fresh state
                rng, rk2, o_idx2 = draw(rng)
                inflight2 = algo.round_start(
                    state, mk_ctx(ctx0, o_idx2, step_next), rk2)
                return (state, rng, inflight2, rk2, o_idx2, step_next), m

            rest = (None if plan is None
                    else jax.tree.map(lambda v: v[1:], plan))
            carry = (state, rng, inflight, rk, o_idx, step0)
            (state, rng, inflight, rk, o_idx, step0), ms = jax.lax.scan(
                body, carry, rest, length=k - 1)
            # epilogue: round k-1's finish (its start was the last body's —
            # or the prologue's, when k == 1 and the scan runs zero bodies)
            state, m_last = algo.round_finish(
                state, mk_ctx(ctx0, o_idx, step0), inflight, rk)
            ms = jax.tree.map(
                lambda a, b: jnp.concatenate([a, b[None]], axis=0),
                ms, m_last)
            return state, rng, ms

        if overlap:
            chunk_fn = chunk_fn_pipelined
        kw = {}
        if self.donate_state:
            kw["donate_argnums"] = (0,)
        shard_fn = getattr(algo, "shardings", None)
        if self.mesh is not None and shard_fn is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            probe = (dataclasses.replace(ctx0, o_idx=jnp.zeros((n_r,),
                                                               jnp.int32))
                     if uses_open else ctx0)
            state_sh, ctx_sh = shard_fn(self.mesh, state, probe)
            if uses_open:
                # o_idx is drawn inside the scan; the ctx argument omits it
                ctx_sh = dataclasses.replace(ctx_sh, o_idx=EMPTY)
            rep = NamedSharding(self.mesh, PartitionSpec())
            plan_sh = jax.tree.map(lambda _: rep, plan)
            kw["in_shardings"] = (state_sh, ctx_sh, None, plan_sh)
            # as in _build_round: the next chunk consumes this chunk's state
            kw["out_shardings"] = (state_sh, None, None)
        return jax.jit(chunk_fn, **kw)

    def _get_chunk(self, k: int, n_open: int, n_r: int, state: RoundState,
                   ctx0: BatchCtx, plan, overlap: bool = False):
        # `overlap` keys the cache: each schedule holds its own compiled
        # program, so toggling it between runs is a dict hit, not a
        # recompile (pinned by tests/test_overlap.py's JitCacheWatch)
        key = (k, n_open, n_r, overlap,
               jax.tree_util.tree_structure((state, ctx0, plan)))
        fn = self._chunk_cache.get(key)
        if fn is None:
            fn = self._chunk_cache[key] = self._build_chunk(
                k, n_open, n_r, state, ctx0, plan, overlap=overlap)
        return fn

    # ------------------------------------------------------------- setup ----
    def init(self, model_init: Callable, data, rng=None) -> RoundState:
        """Fresh-training entry point: also resets ``rounds_done`` and
        ``history`` so a reused engine doesn't fast-forward the new run's
        RNG stream past the previous training's rounds (resume goes through
        ``load_state``, which restores both instead)."""
        if rng is None:
            rng = jax.random.PRNGKey(self.algo.hp.seed)
        self.rounds_done = 0
        self.history = []
        return self.algo.init(rng, model_init, data)

    def make_ctx(self, data, o_idx=EMPTY, weights=EMPTY,
                 active_budget=None, cohort=EMPTY,
                 population=None) -> BatchCtx:
        open_x = data.open_x if self.algo.uses_open else EMPTY
        return BatchCtx(x=data.x_clients, y=data.y_clients,
                        open_x=open_x, o_idx=o_idx, weights=weights,
                        cohort=cohort, active_budget=active_budget,
                        population=population)

    # --------------------------------------------------------------- run ----
    def run(self, state: RoundState, data, rounds: Optional[int] = None,
            weights=EMPTY, log_every: int = 1,
            start_round: Optional[int] = None, chunk_rounds: int = 1,
            ctx_plan=None, active_budget: Optional[int] = None,
            cohort=EMPTY, population: Optional[int] = None,
            overlap: bool = False) -> RoundState:
        """Run ``rounds`` federated rounds starting at ``start_round``
        (default: ``self.rounds_done``, which ``load_state`` restores from a
        checkpoint).  The per-round RNG chain is fast-forwarded past the
        rounds already run, so a save/load/run sequence — or repeated
        ``run(rounds=1)`` calls on one engine — continues the exact key
        stream (and round numbering) an uninterrupted run would produce.

        ``chunk_rounds=k`` folds k rounds at a time into one compiled
        ``lax.scan`` (bitwise identical to the default per-round loop; see
        ``_build_chunk``).  With ``eval_fn`` set, chunk boundaries snap to
        ``log_every`` so every eval still sees the exact log-point state.
        The per-round host hooks (``on_round``/``on_ctx``) force the loop
        path — schedulers that can plan a whole chunk a priori pass
        ``ctx_plan`` instead: a dict of per-round BatchCtx field overrides
        (e.g. ``{"mask": (rounds, K), "stale": (rounds, K)}``) consumed by
        both paths.

        ``active_budget=m`` turns masked rounds participation-sparse: the
        algorithms compute only (at most) m gathered client lanes per round
        instead of the full K-stack — bitwise identical, ~K/m cheaper.  It
        is static (BatchCtx metadata), so it composes with ``chunk_rounds``
        and ``ctx_plan``; the caller guarantees every served mask has at
        most m participants (`repro.sim` schedulers do, by construction).

        ``cohort``/``population`` run the rounds cohort-resident: ``data``
        and ``state.clients`` carry an (S, ...) slab over the (S,) global
        ids in ``cohort``, and ``population`` is the true fleet size K used
        for per-client key derivation (see ``BatchCtx``).  The engine's own
        machinery — treedef-keyed round caches, fused scan, ctx plans,
        sparse budget — is oblivious to the distinction; the host-side
        slab orchestration lives in `repro.sim.runner.CohortRunner`.

        ``overlap=True`` runs the fused chunks on the software-pipelined
        schedule: each scan body finishes round r and immediately issues
        round r+1's logit exchange (``algo.round_start``), double-buffering
        the in-flight upload tensors so the cross-pod all-gather can hide
        behind the next round's private-data compute (see ``_build_chunk``).
        Bitwise identical to ``overlap=False`` — the pinned baseline — and
        requires the algorithm to expose the ``round_start``/``round_finish``
        halves; the per-round loop path has nothing to pipeline and falls
        back to the sequential round with a warning."""
        hp = self.algo.hp
        if overlap and getattr(self.algo, "round_start", None) is None:
            raise ValueError(
                f"overlap=True needs algorithm {self.algo.name!r} to expose "
                f"round_start/round_finish (the pipelined round halves); "
                f"{type(self.algo).__name__} has no round_start")
        rounds = hp.rounds if rounds is None else rounds
        start = self.rounds_done if start_round is None else start_round
        if ctx_plan is not None:
            for f, v in ctx_plan.items():
                if _leading_dim(v) < rounds:
                    # fail loudly on both paths: jnp's clamped indexing would
                    # silently reuse the last plan row on the loop path while
                    # lax.scan raised on the scanned one
                    raise ValueError(
                        f"ctx_plan[{f!r}] covers {_leading_dim(v)} rounds; "
                        f"run() needs {rounds}")
            mask_plan = ctx_plan.get("mask")
            if (active_budget is not None and mask_plan is not None
                    and active_budget < mask_plan.shape[-1]):
                # the sparse-round contract, enforced loudly while the plan
                # is still host-side: every round needs 1 <= participants <=
                # budget.  Overflow would silently skip clients that carry
                # aggregation weight; an empty round's aggregation falls
                # back to uniform-over-K, which needs the uploads the
                # sparse plane never computes.  Checked in numpy — the sim
                # path calls run() once per fused chunk, and device
                # reductions here would add blocking host syncs to a loop
                # whose whole point is one sync per chunk
                pops = (np.asarray(mask_plan) > 0).sum(axis=-1)
                lo, hi = int(pops.min()), int(pops.max())
                if lo < 1 or hi > active_budget:
                    raise ValueError(
                        f"active_budget={active_budget} needs 1 <= "
                        f"participants <= budget every round; ctx_plan "
                        f"masks have [{lo}, {hi}]")
        rng = jax.random.PRNGKey(hp.seed)
        if start:
            rng = _fast_forward_key(rng, start)
        if self.algo.uses_open:
            n_open = _leading_dim(data.open_x)
            n_r = min(hp.open_batch, n_open)
        else:
            n_open = n_r = 0
        chunk = self._effective_chunk(chunk_rounds)
        if chunk > 1:
            if self.eval_fn is not None and log_every < chunk:
                import warnings
                warnings.warn(
                    f"eval_fn snaps every scan segment to log_every="
                    f"{log_every} rounds, discarding most of the requested "
                    f"chunk_rounds={chunk} fusion (each eval needs a host "
                    f"sync); pass log_every=chunk_rounds to actually fuse",
                    stacklevel=2)
            return self._run_scanned(state, data, rounds, weights, log_every,
                                     start, rng, chunk, ctx_plan, n_open, n_r,
                                     active_budget, cohort, population,
                                     overlap)
        if overlap:
            import warnings
            warnings.warn(
                "overlap=True only pipelines the fused scan path; the "
                "per-round loop (chunk_rounds<=1, or per-round host hooks) "
                "has nothing to double-buffer and runs the sequential "
                "round — which is bitwise the same schedule anyway",
                stacklevel=2)
        fn = None
        for r in range(start, start + rounds):
            with obs.span("engine.sample", "engine", round=r):
                rng, rk, ri = jax.random.split(rng, 3)
                o_idx = (jax.random.choice(ri, n_open, (n_r,), replace=False)
                         if self.algo.uses_open else EMPTY)
                ctx = self.make_ctx(data, o_idx=o_idx, weights=weights,
                                    active_budget=active_budget,
                                    cohort=cohort, population=population)
                if ctx_plan is not None:
                    ctx = dataclasses.replace(
                        ctx, **{f: v[r - start] for f, v in ctx_plan.items()})
            if self.on_ctx is not None:
                # externally-supplied client subsets: a `repro.sim` scheduler
                # (or any caller) rewrites the ctx — participation mask,
                # staleness, weights — before the jitted round sees it.
                # Only this hook can change the ctx *structure* round-to-
                # round, so only here is the cached round re-resolved (a
                # host-side pytree flatten) every round
                ctx = self.on_ctx(r, ctx)
                fn = self._get_round(state, ctx)
            elif fn is None:
                fn = self._get_round(state, ctx)
            with obs.span("engine.round", "engine", round=r):
                state, m = fn(state, ctx, rk)
                if self.on_round is not None:
                    state = self.on_round(r, state)
                self.last_metrics = m
                self.rounds_done = r + 1
                if self.on_chunk is not None:
                    self.on_chunk(self.rounds_done, state)
                if (r + 1) % log_every == 0:
                    with obs.span("engine.sync", "engine"):
                        rec = {"round": r + 1,
                               **{k: float(v) for k, v in m.items()
                                  if jnp.ndim(v) == 0}}
                    if self.eval_fn is not None:
                        with obs.span("engine.eval", "engine"):
                            rec.update(self.eval_fn(
                                *self.algo.eval_params(state)))
                    self.history.append(rec)
        reg = obs.current_registry()
        if reg is not None:
            reg.counter("engine.rounds").inc(rounds)
        return state

    def _effective_chunk(self, chunk_rounds: int) -> int:
        """Clamp the requested chunk: the per-round host hooks (and a
        manually overridden ``_round``) cannot run inside a scan."""
        chunk = max(1, int(chunk_rounds))
        if (self.on_round is not None or self.on_ctx is not None
                or self._round is not None):
            return 1
        return chunk

    def _run_scanned(self, state, data, rounds, weights, log_every, start,
                     rng, chunk, ctx_plan, n_open, n_r, active_budget=None,
                     cohort=EMPTY, population=None,
                     overlap: bool = False) -> RoundState:
        r, end, n_chunks = start, start + rounds, 0
        while r < end:
            k = min(chunk, end - r)
            n_chunks += 1
            if self.eval_fn is not None:
                # eval needs the state at every log point: snap the segment
                # to end exactly on the next log boundary
                k = min(k, (r // log_every + 1) * log_every - r)
            plan = (None if ctx_plan is None else
                    {f: v[r - start:r - start + k]
                     for f, v in ctx_plan.items()})
            # the chunk draws its keys and o_r on device: the host's part of
            # sampling is the context and the chunk's program lookup
            with obs.span("engine.sample", "engine", round=r):
                ctx0 = self.make_ctx(data, weights=weights,
                                     active_budget=active_budget,
                                     cohort=cohort, population=population)
                fn = self._get_chunk(k, n_open, n_r, state, ctx0, plan,
                                     overlap=overlap)
            # the span covers dispatch through the chunk's one host sync
            # (device_get below) — all instrumentation sits OUTSIDE the
            # compiled scan, so the fused path stays bitwise identical and
            # keeps its one-sync-per-chunk discipline
            with obs.span("engine.chunk", "engine", rounds=k, start_round=r,
                          overlap=overlap):
                state, rng, ms = fn(state, ctx0, rng, plan)
                self.last_metrics = {key: v[-1] for key, v in ms.items()}
                # one host sync per chunk: the stacked per-round scalars land
                # together instead of one float() device round-trip per round
                with obs.span("engine.sync", "engine"):
                    scalars = jax.device_get({key: v for key, v in ms.items()
                                              if jnp.ndim(v) == 1})
            for i in range(k):
                if (r + i + 1) % log_every != 0:
                    continue
                rec = {"round": r + i + 1,
                       **{key: float(v[i]) for key, v in scalars.items()}}
                if self.eval_fn is not None:   # i == k - 1 by the snap above
                    with obs.span("engine.eval", "engine"):
                        rec.update(self.eval_fn(
                            *self.algo.eval_params(state)))
                self.history.append(rec)
            r += k
            self.rounds_done = r
            if self.on_chunk is not None:
                self.on_chunk(self.rounds_done, state)
        reg = obs.current_registry()
        if reg is not None:
            reg.counter("engine.rounds").inc(rounds)
            reg.counter("engine.chunks").inc(n_chunks)
        return state

    # -------------------------------------------------------- comm bytes ----
    def _payload_ctx(self, data) -> BatchCtx:
        if self.algo.uses_open:
            n_r = min(self.algo.hp.open_batch, _leading_dim(data.open_x))
            o_idx = jnp.zeros((n_r,), jnp.int32)
        else:
            o_idx = EMPTY
        return self.make_ctx(data, o_idx=o_idx)

    def measured_leg_bytes(self, state: RoundState, data) -> tuple[int, int]:
        """(uplink bytes per client, downlink broadcast bytes) measured on
        the actually-encoded payload pytree via ``eval_shape`` (free).  The
        legs differ under a per-leg `wire.AsymmetricCodec` (sparse upload,
        dense broadcast); the `repro.sim` clock charges each separately."""
        with obs.span("wire.measure", "wire",
                      codec=self.codec.name) as sp:
            ctx = self._payload_ctx(data)
            up = jax.eval_shape(
                lambda s, c: self.codec.encode_up(
                    self.algo.upload_payload(s, c)), state, ctx)
            down = jax.eval_shape(
                lambda s, c: self.codec.encode_down(
                    self.algo.upload_payload(s, c)), state, ctx)
            up_b, down_b = nbytes(up), nbytes(down)
            sp.set(up_bytes=up_b, down_bytes=down_b)
        return up_b, down_b

    def measured_round_bytes(self, state: RoundState, data,
                             n_clients: Optional[int] = None) -> int:
        """Per-round wire bytes of this algorithm under ``self.codec``,
        measured on the actually-encoded payload pytree (via ``eval_shape``,
        so it costs no compute): K client uploads + 1 multicast broadcast —
        the convention `comm.CommModel` uses (for symmetric codecs both legs
        encode identically, so this is payload * (K + 1) exactly)."""
        K = _leading_dim(data.x_clients) if n_clients is None else n_clients
        up, down = self.measured_leg_bytes(state, data)
        return up * K + down

    # ----------------------------------------------------------- telemetry --
    def compile_counts(self) -> dict:
        """Compiled-program accounting (`obs.engine_compile_counts`): how
        many round/chunk signatures this engine built and how many programs
        their jits compiled — after warmup each signature should hold at
        exactly one program (the serve-engine discipline, CI-pinned by
        ``benchmarks/obs_smoke.py``)."""
        from ..obs import engine_compile_counts
        return engine_compile_counts(self)

    def compiled_hlo(self, state: RoundState, data) -> list[str]:
        """Optimized HLO text of every round and fused-chunk program this
        engine has built, compiled for ``state`` and ``data``'s plain
        context (runs made with weights, a ctx plan or a sparse budget
        built programs this does not lower).  What the device really runs:
        a kernel's ``tpu_custom_call``, a cross-chip collective."""
        rng = jax.random.PRNGKey(self.algo.hp.seed)
        lowered = [fn.lower(state, self._payload_ctx(data), rng)
                   for fn in self._round_cache.values()]
        lowered += [fn.lower(state, self.make_ctx(data), rng, None)
                    for fn in self._chunk_cache.values()]
        return [lo.compile().as_text() for lo in lowered]

    # ------------------------------------------------------- checkpointing --
    def save_state(self, path: str, state: RoundState) -> None:
        import numpy as np
        leaves = jax.tree_util.tree_flatten(state)[0]
        tag = np.frombuffer(self.algo.name.encode(), dtype=np.uint8)
        hist = np.frombuffer(json.dumps(self.history, default=float).encode(),
                             dtype=np.uint8)
        save_pytree(path, {"algo": tag, "leaves": leaves,
                           "round": np.int64(self.rounds_done),
                           "history": hist})

    def load_state(self, path: str, like: RoundState,
                   shardings=None) -> RoundState:
        """Restore a state saved by ``save_state``.  ``like`` supplies the
        treedef (e.g. a freshly-inited state of the same algorithm);
        ``shardings`` (a pytree of `jax.sharding.Sharding` matching the
        state, e.g. from ``algo.shardings``) places each leaf directly onto
        its shards.  Also restores ``rounds_done`` and ``history`` so a
        subsequent ``run`` resumes the RNG stream where the checkpoint
        left off."""
        import numpy as np
        raw = load_pytree(path)
        tag = bytes(np.asarray(raw["algo"]).tobytes()).decode()
        if tag != self.algo.name:
            raise ValueError(f"checkpoint is for {tag!r}, "
                             f"engine runs {self.algo.name!r}")
        treedef = jax.tree_util.tree_structure(like)
        n_like = treedef.num_leaves
        if len(raw["leaves"]) != n_like:
            raise ValueError(
                f"checkpoint {path!r} holds {len(raw['leaves'])} leaves but "
                f"the engine's state has {n_like} — it was saved from a "
                f"different arch/config than this {self.algo.name!r} state")
        state = jax.tree_util.tree_unflatten(treedef, raw["leaves"])
        # fail HERE, naming the mismatched leaves, instead of later inside a
        # jitted round with an opaque XLA shape error
        assert_tree_compatible(like, state, what=f"checkpoint {path!r}")
        if shardings is not None:
            state = jax.tree.map(lambda a, s: jax.device_put(a, s),
                                 state, shardings)
        if "round" in raw:
            self.rounds_done = int(np.asarray(raw["round"]))
        if "history" in raw:
            self.history = json.loads(
                bytes(np.asarray(raw["history"]).tobytes()).decode())
        return state
