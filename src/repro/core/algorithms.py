"""Unified `FedAlgorithm` API (the substrate every scenario plugs into).

Every federated protocol in the repo — DS-FL (paper Algorithm 1), FD
(Jeong et al. 2018) and FedAvg (McMahan et al. 2017) — exposes the same
two-method surface:

    state            = algo.init(rng, model_init, data)   # -> RoundState
    state, metrics   = algo.round(state, ctx, rng)        # one federated round

`RoundState` / `ClientState` / `ServerState` are frozen dataclasses
registered as JAX pytrees, so one `jax.jit(algo.round)` covers any
algorithm (see `repro.core.engine.FedEngine`) and replaces the positional
``wk, sk, ouk, odk, wg, sg, odg`` soup of the original per-protocol round
builders.  `BatchCtx` carries the per-round data (private stacks, open
batch indices, FedAvg weights) as a single pytree argument.

Algorithms additionally expose:

  * ``uses_open``                — whether the engine must sample o_r;
  * ``upload_payload(state, ctx)`` — the per-client wire payload of one
    round (per-sample logits for DS-FL, per-class logits for FD, the full
    parameter vector for FedAvg), which `repro.core.wire` codecs encode and
    measure against `comm.CommModel`'s analytic byte counts;
  * ``eval_params(state)``       — the (params, model_state) pair a test-set
    evaluation should score (server model for DS-FL/FedAvg, mean client
    model for FD, which has no server model).

RNG discipline mirrors the (fixed) reference `protocol.make_dsfl_round`
bit-for-bit: the DS-FL round splits its key into (update, client-distill,
corrupt, server-distill) so the golden-parity test in
``tests/test_engine.py`` can compare the two engines exactly.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Callable, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp

from ..optim import optimizers as opt_lib
from . import fd as fd_lib
from .aggregation import (aggregate, participation_weights, weighted_era,
                          weighted_sa)
from .client import LocalSpec, local_distill, local_update, predict_probs
from .fedavg import weighted_average
from .hierarchy import hierarchical_weighted_era, hierarchical_weighted_sa
from .losses import entropy, pinned_mean, pinned_sum
from .prng import split_take
from .protocol import DSFLConfig  # noqa: F401  (re-exported as part of the API)

EMPTY = ()   # absent pytree slot (contributes no leaves)

# `jax.named_scope` names of the DS-FL round's steps: op metadata only (the
# HLO ops, and so every result, are the same with or without them); the
# device trace keeps them on each op, where the benchmark splits the
# round's device time by step
UPDATE = "dsfl_update"                  # 1. local update on private data
PREDICT = "dsfl_predict"                # 2. prediction on o_r
AGGREGATE = "dsfl_aggregate"            # 3-5. teacher: SA / ERA
DISTILL = "dsfl_distill"                # 6. clients' distillation
SERVER_DISTILL = "dsfl_server_distill"  # 6'. the server's distillation


def _pytree_dataclass(cls=None, *, meta=()):
    """Register a frozen dataclass as a JAX pytree.  ``meta`` names fields
    that are *static* (part of the treedef, not traced leaves) — e.g.
    ``BatchCtx.active_budget``, which fixes array shapes and must therefore
    be a Python int at trace time.  A changed meta value changes the treedef,
    so `FedEngine`'s treedef-keyed jit caches recompile automatically."""
    def wrap(c):
        fields = [f.name for f in dataclasses.fields(c) if f.name not in meta]
        return jax.tree_util.register_dataclass(c, data_fields=fields,
                                                meta_fields=list(meta))
    return wrap(cls) if cls is not None else wrap


# --------------------------------------------------------------- states ------
@_pytree_dataclass
@dataclass(frozen=True)
class ClientState:
    """Per-client persistent state, stacked over the leading (K,) axis."""
    params: Any = EMPTY         # model parameters, leaves (K, ...)
    model_state: Any = EMPTY    # e.g. BatchNorm running stats
    opt_update: Any = EMPTY     # optimizer state of the "1. Update" loop
    opt_distill: Any = EMPTY    # optimizer state of the "6. Distillation" loop


@_pytree_dataclass
@dataclass(frozen=True)
class ServerState:
    """Global-model state held by the server (empty for FD)."""
    params: Any = EMPTY
    model_state: Any = EMPTY
    opt_distill: Any = EMPTY


@_pytree_dataclass
@dataclass(frozen=True)
class RoundState:
    clients: ClientState = ClientState()
    server: ServerState = ServerState()


@_pytree_dataclass(meta=("active_budget", "population"))
@dataclass(frozen=True)
class BatchCtx:
    """Per-round data context (a single pytree argument to ``round``).

    ``mask``/``stale`` are the partial-participation fields the `repro.sim`
    schedulers fill in: absent clients (mask 0) neither train nor contribute
    to aggregation that round, and stale contributions (an async client that
    last synced its global labels ``stale`` aggregations ago) are discounted
    by the algorithm's ``staleness_decay``.  Left EMPTY, the round is the
    exact bit-pinned full-participation path.

    ``active_budget`` is the participation-sparse compute budget: a *static*
    upper bound m on how many clients can be active in any round this ctx
    serves (pytree metadata, so shapes stay static and the round still fuses
    into the engine's ``lax.scan``).  When set below K alongside ``mask``,
    the algorithms gather the m active lanes out of the (K, ...) client
    stack, run update/predict/distill on only those, and scatter results
    back — a ~K/m per-round compute and activation-memory reduction that is
    **bitwise identical** to the dense masked round (padding lanes carry
    exactly zero aggregation weight).  ``None`` (default) keeps the dense
    path.  Contract: ``1 <= popcount(mask) <= active_budget`` — schedulers
    guarantee both by construction (`repro.sim.scheduler`; a zero-
    participant round's aggregation falls back to uniform-over-K, which
    needs the very uploads the sparse plane skips — `FedEngine.run` and
    `SimRunner` reject violating plans loudly).

    ``cohort``/``population`` are the cohort-resident round plane: when
    ``cohort`` carries an (S,) int array of *global client ids*, the leading
    client axis of every per-client field (``x``/``y``/``mask``/``stale``/
    the client stack in `RoundState`) is an O(m) **slab** over those ids
    rather than the full population — client state streams through a host-
    side `repro.core.cohort.ClientStore` between rounds, and resident
    memory stops depending on K entirely.  ``population`` (static metadata)
    is the true fleet size K: per-client RNG keys are derived as rows
    ``cohort`` of ``split(r, population)`` (`core.prng.split_take`, O(S)),
    so a client consumes bitwise the same key stream whichever slab lane it
    lands in — the invariant that makes small-K cohort-resident rounds
    bitwise identical to the dense masked rounds (tests/test_cohort.py)."""
    x: Any = EMPTY          # (K, I_k, ...) private inputs
    y: Any = EMPTY          # (K, I_k) private labels
    open_x: Any = EMPTY     # (I_o, ...) the full shared open set
    o_idx: Any = EMPTY      # (n,) this round's open-batch indices o_r
    weights: Any = EMPTY    # (K,) client dataset sizes (FedAvg Eq. 3)
    mask: Any = EMPTY       # (K,) 0/1 participation this round
    stale: Any = EMPTY      # (K,) rounds since each client last synced
    cohort: Any = EMPTY     # (S,) global client id of each slab lane
    active_budget: Optional[int] = None   # static per-round activity bound m
    population: Optional[int] = None      # static fleet size K (cohort mode)


# ------------------------------------------------------------- protocol ------
@runtime_checkable
class FedAlgorithm(Protocol):
    """The algorithm surface `FedEngine` drives.  ``hp`` must provide
    ``rounds`` and ``seed``; ``uses_open`` algorithms also ``open_batch``."""
    name: str
    uses_open: bool

    def init(self, rng, model_init: Callable, data) -> RoundState: ...

    def round(self, state: RoundState, ctx: BatchCtx,
              rng) -> tuple[RoundState, dict]: ...

    def upload_payload(self, state: RoundState, ctx: BatchCtx): ...

    def eval_params(self, state: RoundState): ...


def _stack_init(model_init: Callable, key, K: int):
    return jax.vmap(model_init)(jax.random.split(key, K))


def _first_client(tree):
    return jax.tree.map(lambda a: a[0], tree)


def present(slot) -> bool:
    """Whether an optional BatchCtx slot carries an array (EMPTY is ``()``).
    A Python-level (trace-time) predicate: ctx pytree structure is static
    under jit, so the masked and full-participation paths compile
    separately and the latter stays bit-identical to the seed round."""
    return not isinstance(slot, tuple)


def select_clients(mask, new_tree, old_tree):
    """Per-leaf ``where`` over the leading client axis: participants take the
    freshly-computed leaves, absent clients keep their previous state.
    Vectorized (one fused where per leaf, no per-client Python loop)."""
    m = mask.astype(bool)

    def sel(n, o):
        mb = m.reshape((m.shape[0],) + (1,) * (n.ndim - 1))
        return jnp.where(mb, n, o)

    return jax.tree.map(sel, new_tree, old_tree)


def client_keys(rng, ctx: BatchCtx, K: int):
    """The (K, 2) per-client keys of one round leg.  Dense populations draw
    the house discipline's ``split(rng, K)``; a cohort slab draws rows
    ``ctx.cohort`` of ``split(rng, population)`` instead (O(S), bitwise the
    same rows — `core.prng.split_take`), so per-client randomness is a
    function of the *global* client id, never of slab placement."""
    if present(ctx.cohort):
        return split_take(rng, ctx.cohort, ctx.population)
    return jax.random.split(rng, K)


def masked_mean(values, mask):
    """Mean of ``values`` over the mask-1 lanes, reduction order pinned
    across programs (`losses.pinned_mean`): the dense masked round and the
    participation-sparse round are two different XLA programs reducing
    bitwise-identical (K,) inputs, and a plain fused reduce is free to
    reassociate differently in each — a dot-lowered sum is not."""
    return pinned_mean(values, mask.astype(jnp.float32))


# --------------------------------------------- participation-sparse plane ----
def active_indices(mask, budget: int):
    """Jit-safe gather indices for the participation-sparse round:
    (K,) mask -> (budget,) client indices.  A stable argsort over the 0/1
    activity key puts participants first *in ascending client order* and
    pads the remaining lanes with distinct non-participants — so a scatter
    back via ``.at[idx].set`` never collides, and padding lanes land on
    mask-0 clients whose results `select_clients` discards anyway.
    Requires ``budget >= popcount(mask)`` (the scheduler contract); with
    fewer lanes than participants, the overflow clients would silently keep
    stale state while still carrying aggregation weight."""
    key = jnp.where(mask > 0, jnp.int32(0), jnp.int32(1))
    return jnp.argsort(key, stable=True)[:budget]


def gather_clients(tree, idx):
    """Per-leaf gather of the ``idx`` lanes along the leading client axis:
    the (m, ...) active slice of a (K, ...) client stack."""
    return jax.tree.map(lambda a: jnp.take(a, idx, axis=0), tree)


def scatter_clients(new_tree, old_tree, idx):
    """Write the computed (m, ...) lanes back into the (K, ...) stack.
    ``idx`` lanes take the fresh leaves, all other clients keep their
    previous state — the sparse-plane counterpart of `select_clients`."""
    return jax.tree.map(lambda n, o: o.at[idx].set(n), new_tree, old_tree)


def scatter_zeros(values_m, K: int, idx):
    """Scatter (m, ...) per-lane results into an exact-zero (K, ...) buffer.
    The untouched lanes are *exactly* 0.0, so any downstream reduction that
    multiplies them by a zero participation weight is bitwise identical to
    the dense masked computation (0.0 * x == 0.0 == 0.0 * 0.0 for finite
    x) — the property the sparse round's bitwise-parity guarantee rides on."""
    return jnp.zeros((K,) + values_m.shape[1:], values_m.dtype
                     ).at[idx].set(values_m)


# ---------------------------------------------------------------- DS-FL ------
@dataclass(frozen=True)
class DSFLAlgorithm:
    """Paper Algorithm 1 on the unified API (SA / ERA / weighted-ERA).

    ``corrupt(probs (K, n, C), xo, rng) -> probs`` optionally injects
    malicious local logits between "2. Prediction" and "4. Aggregation".

    ``use_kernel=True`` routes "4. Aggregation" through the fused Pallas
    mean+sharpen kernels — including the *weighted* variant on the masked
    partial-participation (`repro.sim`) and weighted-ERA paths, which
    previously always fell back to einsum+softmax (two extra HBM passes
    over the (K, n, C) logit stack).  Default False: the pure-jnp route,
    bit-pinned against the seed engine.

    ``agg_edges > 1`` routes "4. Aggregation" through the two-level edge →
    server tree (`core.hierarchy`): globally-normalized weights, per-edge
    partial sums, server sharpen.  ``agg_edges=1`` (default) is bitwise the
    flat path; deeper trees carry `core.hierarchy`'s tolerance contract.
    """
    apply_fn: Callable
    hp: DSFLConfig
    corrupt: Optional[Callable] = None
    agg_weights: Optional[jax.Array] = None   # for aggregation="weighted_era"
    use_kernel: bool = False
    agg_edges: int = 1

    name = "dsfl"
    uses_open = True

    def _specs(self):
        hp = self.hp
        opt_u = opt_lib.make(hp.optimizer, hp.lr)
        opt_d = opt_lib.make(hp.optimizer, hp.lr_distill)
        spec_u = LocalSpec(self.apply_fn, opt_u, hp.local_epochs, hp.batch_size)
        spec_d = LocalSpec(self.apply_fn, opt_d, hp.distill_epochs,
                           min(hp.batch_size, hp.open_batch))
        return spec_u, spec_d

    def init(self, rng, model_init: Callable, data) -> RoundState:
        K = data.x_clients.shape[0]
        wg, sg = model_init(rng)
        wk, sk = _stack_init(model_init, rng, K)
        return self.init_from(wk, sk, wg, sg)

    def init_from(self, wk, sk, wg, sg) -> RoundState:
        """Build a RoundState around externally-initialized model params
        (the seed `DSFLEngine.init_states` contract)."""
        spec_u, spec_d = self._specs()
        return RoundState(
            clients=ClientState(params=wk, model_state=sk,
                                opt_update=jax.vmap(spec_u.opt.init)(wk),
                                opt_distill=jax.vmap(spec_d.opt.init)(wk)),
            server=ServerState(params=wg, model_state=sg,
                               opt_distill=spec_d.opt.init(wg)))

    def init_server(self, rng, model_init: Callable) -> RoundState:
        """Cohort-resident entry point: only the server model materializes
        (same ``rng`` discipline as `init`, so the server state is bitwise
        the dense init's); client slabs stream in via `init_cohort` /
        `repro.core.cohort.ClientStore`."""
        spec_u, spec_d = self._specs()
        wg, sg = model_init(rng)
        return RoundState(server=ServerState(params=wg, model_state=sg,
                                             opt_distill=spec_d.opt.init(wg)))

    def init_cohort(self, rng, model_init: Callable, ids,
                    population: int) -> ClientState:
        """The (|ids|, ...) slab of fresh client states for the given global
        ids: row g of the would-be dense `init` stack is re-derived from g's
        key alone (`core.prng.split_take`), so lazily materializing a
        million-client fleet m clients at a time is bitwise identical to
        gathering rows out of ``_stack_init(model_init, rng, K)``."""
        spec_u, spec_d = self._specs()
        wk, sk = jax.vmap(model_init)(split_take(rng, ids, population))
        return ClientState(params=wk, model_state=sk,
                           opt_update=jax.vmap(spec_u.opt.init)(wk),
                           opt_distill=jax.vmap(spec_d.opt.init)(wk))

    def _masked_teacher(self, probs, ctx: BatchCtx):
        """"3-5. Upload / Aggregation / Broadcast" of a masked round, over
        the full (K, n, C) upload stack.  Shared verbatim by the dense
        masked path and the participation-sparse path: the sparse plane
        scatters its computed prediction lanes into exact zeros, and every
        reduction here multiplies non-participant lanes by an exact-zero
        weight (``0.0 * x == 0.0`` for the finite probabilities crossing
        the wire) — which is what makes the two paths bitwise identical."""
        hp = self.hp
        agg_w = self.agg_weights
        if agg_w is None and hp.aggregation == "weighted_era":
            # adaptive reliability (paper §5 "future work"): inverse mean
            # entropy of each client's uploaded soft labels — absent lanes
            # get a finite garbage value that the mask zeroes exactly
            ent_k = jnp.mean(entropy(probs), axis=-1)           # (K,)
            agg_w = 1.0 / (ent_k + 1e-3)
        pw = participation_weights(
            ctx.mask, ctx.stale if present(ctx.stale) else None,
            hp.staleness_decay, base=agg_w)
        if self.agg_edges > 1:
            global_logit = (
                hierarchical_weighted_sa(probs, pw, self.agg_edges,
                                         use_kernel=self.use_kernel)
                if hp.aggregation == "sa"
                else hierarchical_weighted_era(probs, pw, hp.temperature,
                                               self.agg_edges,
                                               use_kernel=self.use_kernel))
        else:
            global_logit = (
                weighted_sa(probs, pw, use_kernel=self.use_kernel)
                if hp.aggregation == "sa"
                else weighted_era(probs, pw, hp.temperature,
                                  use_kernel=self.use_kernel))
        # the unsharpened SA diagnostic over the uploads that actually
        # happened: mask-weighted, since absent clients upload nothing
        sa_entropy = jnp.mean(entropy(weighted_sa(probs, ctx.mask)))
        return pw, global_logit, sa_entropy

    def round(self, state: RoundState, ctx: BatchCtx, rng):
        # the fused round IS the composition of its pipeline halves — the
        # same ops in the same order, split at the upload boundary — so the
        # engine's `overlap=True` scan (which issues `round_start` one body
        # early) is bitwise the sequential round by construction
        return self.round_finish(state, ctx,
                                 self.round_start(state, ctx, rng), rng)

    def _is_sparse(self, ctx: BatchCtx) -> bool:
        """Static predicate routing a round through the participation-sparse
        gather plane (`corrupt` sees the full upload stack, so it keeps the
        dense path — attack evaluation is not a perf path).  Shared by both
        halves so a split round can never disagree about its plane."""
        K = ctx.x.shape[0]
        return (present(ctx.mask) and ctx.active_budget is not None
                and ctx.active_budget < K and self.corrupt is None)

    def round_start(self, state: RoundState, ctx: BatchCtx, rng):
        """"1. Update" + "2. Prediction": everything up to (and including)
        the round's upload — the leg that depends only on the round's input
        state.  Returns the in-flight `(wk, sk, ouk, up_loss, probs)`
        buffers `round_finish` consumes (m-lane on the sparse plane).  Both
        halves draw the full ``split(rng, 4)`` so every sub-key lands on
        bitwise the fused round's consumer."""
        spec_u, _ = self._specs()
        wk, sk = state.clients.params, state.clients.model_state
        ouk = state.clients.opt_update
        K = ctx.x.shape[0]
        masked = present(ctx.mask)
        if self._is_sparse(ctx):
            return self._sparse_start(state, ctx, rng, ctx.active_budget)
        r1, _r2, r3, _r4 = jax.random.split(rng, 4)
        with jax.named_scope(PREDICT):
            xo = jnp.take(ctx.open_x, ctx.o_idx, axis=0)

        # 1. Update (always computed for the full stack — a fused where keeps
        # absent clients' state; no per-client Python loop, shards cleanly)
        with jax.named_scope(UPDATE):
            wk_n, sk_n, ouk_n, up_loss = jax.vmap(
                lambda w, s, o, xk, yk, rk: local_update(spec_u, w, s, o, xk,
                                                         yk, rk)
            )(wk, sk, ouk, ctx.x, ctx.y, client_keys(r1, ctx, K))
            if masked:
                wk, sk, ouk = select_clients(ctx.mask, (wk_n, sk_n, ouk_n),
                                             (wk, sk, ouk))
            else:
                wk, sk, ouk = wk_n, sk_n, ouk_n

        # 2. Prediction (local logits on o_r)
        with jax.named_scope(PREDICT):
            probs = jax.vmap(lambda w, s: predict_probs(self.apply_fn, w, s,
                                                        xo))(wk, sk)
            if self.corrupt is not None:
                probs = self.corrupt(probs, xo, r3)
        return (wk, sk, ouk, up_loss, probs)

    def round_finish(self, state: RoundState, ctx: BatchCtx, inflight, rng):
        """"3-6'. Upload / Aggregation / Broadcast / Distillation": consume
        the in-flight upload buffers.  ``state`` supplies only what the
        start leg did not touch (distill optimizers + the server model)."""
        hp = self.hp
        _spec_u, spec_d = self._specs()
        odk = state.clients.opt_distill
        wg, sg = state.server.params, state.server.model_state
        odg = state.server.opt_distill
        K = ctx.x.shape[0]
        masked = present(ctx.mask)
        if self._is_sparse(ctx):
            return self._sparse_finish(state, ctx, inflight, rng,
                                       ctx.active_budget)
        _r1, r2, _r3, r4 = jax.random.split(rng, 4)
        with jax.named_scope(PREDICT):
            xo = jnp.take(ctx.open_x, ctx.o_idx, axis=0)
        wk, sk, ouk, up_loss, probs = inflight

        # 3-5. Upload / Aggregation / Broadcast
        with jax.named_scope(AGGREGATE):
            if masked:
                pw, global_logit, sa_entropy = self._masked_teacher(probs,
                                                                    ctx)
            else:
                agg_w = self.agg_weights
                if agg_w is None and hp.aggregation == "weighted_era":
                    # adaptive reliability (paper §5 "future work"): inverse
                    # mean entropy of each client's uploaded soft labels,
                    # re-estimated every round — diffuse (unreliable)
                    # uploads get down-weighted
                    ent_k = jnp.mean(entropy(probs), axis=-1)   # (K,)
                    agg_w = 1.0 / (ent_k + 1e-3)
                pw = agg_w
                if self.agg_edges > 1:
                    w = (jnp.ones((K,), jnp.float32) if agg_w is None
                         else agg_w)
                    global_logit = (
                        hierarchical_weighted_sa(probs, w, self.agg_edges,
                                                 use_kernel=self.use_kernel)
                        if hp.aggregation == "sa"
                        else hierarchical_weighted_era(
                            probs, w, hp.temperature, self.agg_edges,
                            use_kernel=self.use_kernel))
                else:
                    global_logit = aggregate(probs, hp.aggregation,
                                             hp.temperature, weights=agg_w,
                                             use_kernel=self.use_kernel)
                sa_entropy = jnp.mean(entropy(jnp.mean(probs, axis=0)))
            g_entropy = jnp.mean(entropy(global_logit))

        # 6. Distillation (clients, Eq. 10; absent clients keep their state)
        with jax.named_scope(DISTILL):
            wk_n, sk_n, odk_n, d_loss = jax.vmap(
                lambda w, s, o, rk: local_distill(spec_d, w, s, o, xo,
                                                  global_logit, rk)
            )(wk, sk, odk, client_keys(r2, ctx, K))
            if masked:
                wk, sk, odk = select_clients(ctx.mask, (wk_n, sk_n, odk_n),
                                             (wk, sk, odk))
            else:
                wk, sk, odk = wk_n, sk_n, odk_n

        # 6'. server global model (Eq. 11), with its own key r4
        with jax.named_scope(SERVER_DISTILL):
            wg, sg, odg, gd_loss = local_distill(spec_d, wg, sg, odg, xo,
                                                 global_logit, r4)

        metrics = {"update_loss": (masked_mean(up_loss, ctx.mask) if masked
                                   else jnp.mean(up_loss)),
                   "distill_loss": (masked_mean(d_loss, ctx.mask) if masked
                                    else jnp.mean(d_loss)),
                   "server_distill_loss": gd_loss,
                   "global_entropy": g_entropy,
                   "sa_entropy": sa_entropy}
        if pw is not None:
            # normalized per-client aggregation weights (non-scalar: exposed
            # on `FedEngine.last_metrics`, kept out of the scalar history);
            # pinned total so the diagnostic agrees bitwise across the
            # dense-masked and sparse programs like every other reduction
            metrics["agg_weights"] = pw / jnp.maximum(pinned_sum(pw), 1e-9)
        if masked:
            metrics["participants"] = jnp.sum(ctx.mask.astype(jnp.float32))
        new = RoundState(
            clients=ClientState(wk, sk, ouk, odk),
            server=ServerState(wg, sg, odg))
        return new, metrics

    def _sparse_start(self, state: RoundState, ctx: BatchCtx, rng, m: int):
        """Participation-sparse start leg: gather the <= m active lanes of
        the client stack and run "1. Update" / "2. Prediction" vmapped over
        only the (m, ...) slice — ~K/m less client compute and activation
        memory, **bitwise identical** to the dense masked round (pinned by
        tests/test_engine_scan.py): per-client math sees the same inputs
        and the same per-client keys, and padding lanes carry exactly zero
        aggregation weight.  Returns the m-lane in-flight buffers; ``idx``
        is re-derived by the finish leg (a pure, cheap argsort), keeping
        the exchange buffers O(m)."""
        spec_u, _ = self._specs()
        wk, sk = state.clients.params, state.clients.model_state
        ouk = state.clients.opt_update
        K = ctx.x.shape[0]
        # identical key discipline to the dense round (r3 would feed
        # `corrupt`, which forces the dense path; split to keep key parity)
        r1, _r2, _r3, _r4 = jax.random.split(rng, 4)
        with jax.named_scope(PREDICT):
            xo = jnp.take(ctx.open_x, ctx.o_idx, axis=0)

        # 1. Update — only the gathered lanes; per-client keys gathered out
        # of the same (K,) split the dense round draws, so every active
        # client consumes bitwise its dense-path key
        with jax.named_scope(UPDATE):
            idx = active_indices(ctx.mask, m)
            mask_m = jnp.take(ctx.mask, idx, axis=0)
            x_m, y_m = gather_clients((ctx.x, ctx.y), idx)
            wk_m, sk_m, ouk_m = gather_clients((wk, sk, ouk), idx)
            wk_n, sk_n, ouk_n, up_loss = jax.vmap(
                lambda w, s, o, xk, yk, rk: local_update(spec_u, w, s, o, xk,
                                                         yk, rk)
            )(wk_m, sk_m, ouk_m, x_m, y_m,
              jnp.take(client_keys(r1, ctx, K), idx, axis=0))
            wk_m, sk_m, ouk_m = select_clients(mask_m, (wk_n, sk_n, ouk_n),
                                               (wk_m, sk_m, ouk_m))

        # 2. Prediction on the active lanes (the finish leg scatters into
        # exact zeros so the masked aggregation sees its (K, n, C) stack)
        with jax.named_scope(PREDICT):
            probs_m = jax.vmap(lambda w, s: predict_probs(self.apply_fn, w,
                                                          s, xo))(wk_m, sk_m)
        return (wk_m, sk_m, ouk_m, up_loss, probs_m)

    def _sparse_finish(self, state: RoundState, ctx: BatchCtx, inflight,
                       rng, m: int):
        """Participation-sparse finish leg: scatter the in-flight m-lane
        uploads into the shared masked aggregation, distill the gathered
        lanes, and scatter results back into the dense stacks."""
        _spec_u, spec_d = self._specs()
        wk, sk = state.clients.params, state.clients.model_state
        ouk, odk = state.clients.opt_update, state.clients.opt_distill
        wg, sg = state.server.params, state.server.model_state
        odg = state.server.opt_distill
        K = ctx.x.shape[0]
        _r1, r2, _r3, r4 = jax.random.split(rng, 4)
        with jax.named_scope(PREDICT):
            xo = jnp.take(ctx.open_x, ctx.o_idx, axis=0)

        with jax.named_scope(AGGREGATE):
            idx = active_indices(ctx.mask, m)
        with jax.named_scope(DISTILL):
            mask_m = jnp.take(ctx.mask, idx, axis=0)
            odk_m = gather_clients(odk, idx)
        wk_m, sk_m, ouk_m, up_loss, probs_m = inflight

        # 3-5. verbatim the dense masked aggregation on the scattered stack
        with jax.named_scope(AGGREGATE):
            probs = scatter_zeros(probs_m, K, idx)
            pw, global_logit, sa_entropy = self._masked_teacher(probs, ctx)
            g_entropy = jnp.mean(entropy(global_logit))

        # 6. Distillation (clients) on the gathered lanes
        with jax.named_scope(DISTILL):
            wk_n, sk_n, odk_n, d_loss = jax.vmap(
                lambda w, s, o, rk: local_distill(spec_d, w, s, o, xo,
                                                  global_logit, rk)
            )(wk_m, sk_m, odk_m,
              jnp.take(client_keys(r2, ctx, K), idx, axis=0))
            wk_m, sk_m, odk_m = select_clients(mask_m, (wk_n, sk_n, odk_n),
                                               (wk_m, sk_m, odk_m))

        # 6'. server global model (Eq. 11), with its own key r4
        with jax.named_scope(SERVER_DISTILL):
            wg, sg, odg, gd_loss = local_distill(spec_d, wg, sg, odg, xo,
                                                 global_logit, r4)

        with jax.named_scope(DISTILL):
            clients = ClientState(*scatter_clients(
                (wk_m, sk_m, ouk_m, odk_m), (wk, sk, ouk, odk), idx))
        metrics = {"update_loss": masked_mean(scatter_zeros(up_loss, K, idx),
                                              ctx.mask),
                   "distill_loss": masked_mean(scatter_zeros(d_loss, K, idx),
                                               ctx.mask),
                   "server_distill_loss": gd_loss,
                   "global_entropy": g_entropy,
                   "sa_entropy": sa_entropy,
                   "agg_weights": pw / jnp.maximum(pinned_sum(pw), 1e-9),
                   "participants": jnp.sum(ctx.mask.astype(jnp.float32))}
        return RoundState(clients=clients,
                          server=ServerState(wg, sg, odg)), metrics

    def upload_payload(self, state: RoundState, ctx: BatchCtx):
        """One client's upload: per-sample probability vectors on o_r."""
        xo = jnp.take(ctx.open_x, ctx.o_idx, axis=0)
        return predict_probs(self.apply_fn, _first_client(state.clients.params),
                             _first_client(state.clients.model_state), xo)

    def eval_params(self, state: RoundState):
        return state.server.params, state.server.model_state


# ------------------------------------------------------------------- FD ------
@dataclass(frozen=True)
class FDConfig:
    rounds: int = 30
    local_epochs: int = 5
    batch_size: int = 100
    lr: float = 0.1
    optimizer: str = "sgd"
    gamma: float = 1.0          # Eq. 7 distill regularizer weight
    n_classes: int = 10
    seed: int = 0


@dataclass(frozen=True)
class FDAlgorithm:
    """Federated Distillation benchmark (paper §2.2) on the unified API."""
    apply_fn: Callable
    hp: FDConfig

    name = "fd"
    uses_open = False

    def _spec(self):
        hp = self.hp
        return LocalSpec(self.apply_fn, opt_lib.make(hp.optimizer, hp.lr),
                         hp.local_epochs, hp.batch_size)

    def init(self, rng, model_init: Callable, data) -> RoundState:
        K = data.x_clients.shape[0]
        wk, sk = _stack_init(model_init, rng, K)
        return self.init_from(wk, sk)

    def init_from(self, wk, sk) -> RoundState:
        spec = self._spec()
        return RoundState(clients=ClientState(
            params=wk, model_state=sk,
            opt_update=jax.vmap(spec.opt.init)(wk)))

    def init_server(self, rng, model_init: Callable) -> RoundState:
        """FD has no server model: the cohort-resident round state starts
        empty and fills with streamed client slabs."""
        return RoundState()

    def init_cohort(self, rng, model_init: Callable, ids,
                    population: int) -> ClientState:
        """Fresh (|ids|, ...) client slab; bitwise rows of the dense `init`
        stack (see `DSFLAlgorithm.init_cohort`)."""
        spec = self._spec()
        wk, sk = jax.vmap(model_init)(split_take(rng, ids, population))
        return ClientState(params=wk, model_state=sk,
                           opt_update=jax.vmap(spec.opt.init)(wk))

    def round(self, state: RoundState, ctx: BatchCtx, rng):
        hp = self.hp
        spec = self._spec()
        wk, sk = state.clients.params, state.clients.model_state
        ok = state.clients.opt_update
        K = ctx.x.shape[0]
        masked = present(ctx.mask)
        if (masked and ctx.active_budget is not None
                and ctx.active_budget < K):
            return self._sparse_round(state, ctx, rng, ctx.active_budget)
        tk, owns = jax.vmap(
            lambda w, s, xk, yk: fd_lib.per_label_logits(
                self.apply_fn, w, s, xk, yk, hp.n_classes))(wk, sk, ctx.x, ctx.y)
        if masked:
            # absent clients' per-class tables leave the Eq. 5 mean entirely
            owns = jnp.logical_and(owns, ctx.mask.astype(bool)[:, None])
        tg, n_own = fd_lib.aggregate_fd(tk, owns)
        rngs = client_keys(rng, ctx, K)

        def per_client(w, s, o, xk, yk, tkk, rk):
            tgt = fd_lib.distill_targets(tg, tkk, n_own, yk)
            return local_update(spec, w, s, o, xk, yk, rk,
                                distill_extra=tgt, gamma=hp.gamma)

        wk_n, sk_n, ok_n, losses = jax.vmap(per_client)(wk, sk, ok, ctx.x,
                                                        ctx.y, tk, rngs)
        if masked:
            wk, sk, ok = select_clients(ctx.mask, (wk_n, sk_n, ok_n),
                                        (wk, sk, ok))
        else:
            wk, sk, ok = wk_n, sk_n, ok_n
        metrics = {"update_loss": (masked_mean(losses, ctx.mask) if masked
                                   else jnp.mean(losses)),
                   "global_logit": tg}        # (C, C), for Fig. 2 analysis
        return RoundState(clients=ClientState(wk, sk, ok)), metrics

    def _sparse_round(self, state: RoundState, ctx: BatchCtx, rng, m: int):
        """Participation-sparse FD round: per-class tables and the Eq. 7
        update run only on the <= m gathered active lanes; the Eq. 5 mean
        sees scattered zero tables whose ``owns`` rows are False — exactly
        the lanes the dense masked round multiplies by zero."""
        hp = self.hp
        spec = self._spec()
        wk, sk = state.clients.params, state.clients.model_state
        ok = state.clients.opt_update
        K = ctx.x.shape[0]
        idx = active_indices(ctx.mask, m)
        mask_m = jnp.take(ctx.mask, idx, axis=0)
        x_m, y_m = gather_clients((ctx.x, ctx.y), idx)
        wk_m, sk_m, ok_m = gather_clients((wk, sk, ok), idx)

        tk_m, owns_m = jax.vmap(
            lambda w, s, xk, yk: fd_lib.per_label_logits(
                self.apply_fn, w, s, xk, yk, hp.n_classes))(wk_m, sk_m,
                                                            x_m, y_m)
        owns_m = jnp.logical_and(owns_m, mask_m.astype(bool)[:, None])
        # non-gathered lanes scatter as (zeros, False): identical Eq. 5 terms
        # to the dense masked round's (finite table, False-by-mask) lanes
        tg, n_own = fd_lib.aggregate_fd(scatter_zeros(tk_m, K, idx),
                                        scatter_zeros(owns_m, K, idx))
        rngs_m = jnp.take(client_keys(rng, ctx, K), idx, axis=0)

        def per_client(w, s, o, xk, yk, tkk, rk):
            tgt = fd_lib.distill_targets(tg, tkk, n_own, yk)
            return local_update(spec, w, s, o, xk, yk, rk,
                                distill_extra=tgt, gamma=hp.gamma)

        wk_n, sk_n, ok_n, losses = jax.vmap(per_client)(wk_m, sk_m, ok_m,
                                                        x_m, y_m, tk_m, rngs_m)
        wk_m, sk_m, ok_m = select_clients(mask_m, (wk_n, sk_n, ok_n),
                                          (wk_m, sk_m, ok_m))
        wk, sk, ok = scatter_clients((wk_m, sk_m, ok_m), (wk, sk, ok), idx)
        metrics = {"update_loss": masked_mean(scatter_zeros(losses, K, idx),
                                              ctx.mask),
                   "global_logit": tg}
        return RoundState(clients=ClientState(wk, sk, ok)), metrics

    def upload_payload(self, state: RoundState, ctx: BatchCtx):
        """One client's upload: the per-class average logit table (C, C)."""
        t, _ = fd_lib.per_label_logits(
            self.apply_fn, _first_client(state.clients.params),
            _first_client(state.clients.model_state),
            ctx.x[0], ctx.y[0], self.hp.n_classes)
        return t

    def eval_params(self, state: RoundState):
        # FD has no server model: score the mean client model
        mean = lambda t: jax.tree.map(lambda a: jnp.mean(a, axis=0), t)
        return mean(state.clients.params), mean(state.clients.model_state)


# --------------------------------------------------------------- FedAvg ------
@dataclass(frozen=True)
class FedAvgConfig:
    rounds: int = 30
    local_epochs: int = 5
    batch_size: int = 100
    lr: float = 0.1
    optimizer: str = "sgd"
    staleness_decay: float = 0.5    # async: weight factor per round of lag
    seed: int = 0


@dataclass(frozen=True)
class FedAvgAlgorithm:
    """FedAvg benchmark (paper §2.1) on the unified API.  Client state is
    ephemeral (re-broadcast each round); only the server model persists."""
    apply_fn: Callable
    hp: FedAvgConfig

    name = "fedavg"
    uses_open = False

    def _spec(self):
        hp = self.hp
        return LocalSpec(self.apply_fn, opt_lib.make(hp.optimizer, hp.lr),
                         hp.local_epochs, hp.batch_size)

    def init(self, rng, model_init: Callable, data) -> RoundState:
        w0, s0 = model_init(rng)
        return self.init_from(w0, s0)

    def init_from(self, w0, s0) -> RoundState:
        return RoundState(server=ServerState(params=w0, model_state=s0))

    def round(self, state: RoundState, ctx: BatchCtx, rng):
        spec = self._spec()
        w0, s0 = state.server.params, state.server.model_state
        K = ctx.x.shape[0]
        masked = present(ctx.mask)
        sparse = (masked and ctx.active_budget is not None
                  and ctx.active_budget < K)

        def per_client(xk, yk, rk):
            opt_state = spec.opt.init(w0)
            return local_update(spec, w0, s0, opt_state, xk, yk, rk)

        if sparse:
            # client state is ephemeral: only the <= m active lanes train;
            # their results scatter into exact zeros, which the Eq. 3
            # weighted average multiplies by an exact-zero weight anyway
            idx = active_indices(ctx.mask, ctx.active_budget)
            x_m, y_m = gather_clients((ctx.x, ctx.y), idx)
            rngs_m = jnp.take(client_keys(rng, ctx, K), idx, axis=0)
            wk_m, sk_m, _, losses_m = jax.vmap(per_client)(x_m, y_m, rngs_m)
            wk = jax.tree.map(lambda a: scatter_zeros(a, K, idx), wk_m)
            sk = jax.tree.map(lambda a: scatter_zeros(a, K, idx), sk_m)
            losses = scatter_zeros(losses_m, K, idx)
        else:
            rngs = client_keys(rng, ctx, K)
            wk, sk, _, losses = jax.vmap(per_client)(ctx.x, ctx.y, rngs)
        weights = (jnp.ones((K,), jnp.float32)
                   if isinstance(ctx.weights, tuple) else ctx.weights)
        if masked:
            # absent clients carry exactly zero weight in the Eq. 3 average
            # (client state is ephemeral in FedAvg, so masking the average IS
            # the partial-participation round); stale async contributions are
            # discounted FedAsync-style
            weights = participation_weights(
                ctx.mask, ctx.stale if present(ctx.stale) else None,
                self.hp.staleness_decay, base=weights)
        new_w0 = weighted_average(wk, weights)
        new_s0 = weighted_average(sk, weights)
        metrics = {"update_loss": (masked_mean(losses, ctx.mask) if masked
                                   else jnp.mean(losses))}
        if masked:
            metrics["participants"] = jnp.sum(ctx.mask.astype(jnp.float32))
        return RoundState(server=ServerState(new_w0, new_s0)), metrics

    def upload_payload(self, state: RoundState, ctx: BatchCtx):
        """One client's upload: the full parameter vector (+ model state)."""
        return {"params": state.server.params,
                "model_state": state.server.model_state}

    def eval_params(self, state: RoundState):
        return state.server.params, state.server.model_state
