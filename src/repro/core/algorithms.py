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
from .aggregation import (aggregate, entropy_reliability,
                          participation_weights, weighted_sa)
from .client import (LocalSpec, local_distill, local_update, over_clients,
                     predict_probs)
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


@dataclass(frozen=True)
class LanePlan:
    """Which client lanes a round computes, decided once per round.

    * full (``mask`` None): every lane, every result kept;
    * masked: every lane, absent clients' results discarded by
      `select_clients`;
    * sparse (``idx`` set): only the <= m lanes `active_indices` picks,
      gathered out of the (K, ...) stack and scattered back — bitwise the
      masked round (padding lanes carry exactly zero aggregation weight).

    A round body is written once against these operations.  On the full
    plane each returns its argument object unchanged, so the full round's
    program is the plain dense one, op for op."""
    K: int
    mask: Any = None         # (K,) participation; None on the full plane
    idx: Any = None          # (m,) computed lanes; None unless sparse
    lane_mask: Any = None    # participation of the computed lanes

    @classmethod
    def of(cls, K: int, mask=None, budget: Optional[int] = None,
           dense: bool = False) -> "LanePlan":
        """The plane for participation ``mask`` (or None) under the static
        budget m: sparse when ``budget < K``, unless ``dense`` — the
        algorithm's veto for a step that needs every lane's upload."""
        if mask is None:
            return cls(K)
        if dense or budget is None or budget >= K:
            return cls(K, mask, lane_mask=mask)
        idx = active_indices(mask, budget)
        return cls(K, mask, idx, jnp.take(mask, idx, axis=0))

    @classmethod
    def for_round(cls, ctx: BatchCtx, dense: bool = False) -> "LanePlan":
        return cls.of(ctx.x.shape[0], ctx.mask if present(ctx.mask) else None,
                      ctx.active_budget, dense)

    def take(self, tree):
        """A client-axis tree on the computed lanes."""
        return tree if self.idx is None else gather_clients(tree, self.idx)

    def keys(self, rng, ctx: BatchCtx):
        """The computed lanes' rows of `client_keys`: every client consumes
        bitwise its dense-round key."""
        return self.take(client_keys(rng, ctx, self.K))

    def keep(self, new, old):
        """Computed-lane results, absent clients keeping ``old``."""
        if self.mask is None:
            return new
        return select_clients(self.lane_mask, new, old)

    def put(self, lanes, full):
        """Computed lanes written back into the (K, ...) stack ``full``."""
        return lanes if self.idx is None else scatter_clients(lanes, full,
                                                              self.idx)

    def zeros(self, tree):
        """Computed-lane results on all K lanes, exact zeros elsewhere."""
        if self.idx is None:
            return tree
        return jax.tree.map(lambda a: scatter_zeros(a, self.K, self.idx),
                            tree)

    def mean(self, values):
        """The mean of per-lane values (losses) over the participants."""
        if self.mask is None:
            return jnp.mean(values)
        return masked_mean(self.zeros(values), self.mask)


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

    def _teacher(self, probs, ctx: BatchCtx):
        """"3-5. Upload / Aggregation / Broadcast" over the (K, n, C) upload
        stack: (aggregation weights or None, global logit, SA entropy).  On
        a masked round every reduction multiplies absent lanes by an
        exact-zero weight (``0.0 * x == 0.0`` for the finite probabilities
        crossing the wire), so the sparse plane's exact-zero lanes give
        bitwise the dense masked teacher."""
        hp = self.hp
        masked = present(ctx.mask)
        agg_w = self.agg_weights
        if agg_w is None and hp.aggregation == "weighted_era":
            # absent lanes get a finite value that the mask zeroes exactly
            agg_w = entropy_reliability(probs)
        pw = agg_w if not masked else participation_weights(
            ctx.mask, ctx.stale if present(ctx.stale) else None,
            hp.staleness_decay, base=agg_w)
        if self.agg_edges > 1:
            w = jnp.ones((probs.shape[0],), jnp.float32) if pw is None else pw
            global_logit = (
                hierarchical_weighted_sa(probs, w, self.agg_edges,
                                         use_kernel=self.use_kernel)
                if hp.aggregation == "sa"
                else hierarchical_weighted_era(probs, w, hp.temperature,
                                               self.agg_edges,
                                               use_kernel=self.use_kernel))
        else:
            global_logit = aggregate(probs, hp.aggregation, hp.temperature,
                                     weights=pw, use_kernel=self.use_kernel)
        # the unsharpened SA diagnostic over the uploads that actually
        # happened: mask-weighted, since absent clients upload nothing
        sa_entropy = jnp.mean(entropy(
            weighted_sa(probs, ctx.mask) if masked
            else jnp.mean(probs, axis=0)))
        return pw, global_logit, sa_entropy

    def round(self, state: RoundState, ctx: BatchCtx, rng):
        # the fused round IS the composition of its pipeline halves — the
        # same ops in the same order, split at the upload boundary — so the
        # engine's `overlap=True` scan (which issues `round_start` one body
        # early) is bitwise the sequential round by construction
        return self.round_finish(state, ctx,
                                 self.round_start(state, ctx, rng), rng)

    def _lanes(self, ctx: BatchCtx) -> LanePlan:
        """The round's lane plan, the same in both halves.  `corrupt` sees
        the whole upload stack, so it keeps every lane (attack evaluation
        is not a perf path)."""
        return LanePlan.for_round(ctx, dense=self.corrupt is not None)

    def round_start(self, state: RoundState, ctx: BatchCtx, rng):
        """"1. Update" + "2. Prediction": everything up to (and including)
        the round's upload — the leg that depends only on the round's input
        state.  Returns the computed lanes' in-flight `(wk, sk, ouk,
        up_loss, probs)` buffers `round_finish` consumes (O(m) on the
        sparse plane: the finish leg re-derives the lanes).  Both halves
        draw the full ``split(rng, 4)`` so every sub-key lands on bitwise
        the fused round's consumer."""
        spec_u, _ = self._specs()
        c = state.clients
        r1, _r2, r3, _r4 = jax.random.split(rng, 4)
        with jax.named_scope(PREDICT):
            xo = jnp.take(ctx.open_x, ctx.o_idx, axis=0)

        # 1. Update on the computed lanes (absent clients keep their state)
        with jax.named_scope(UPDATE):
            lanes = self._lanes(ctx)
            x, y = lanes.take((ctx.x, ctx.y))
            wk, sk, ouk = lanes.take((c.params, c.model_state, c.opt_update))
            wk_n, sk_n, ouk_n, up_loss = over_clients(
                lambda w, s, o, xk, yk, rk: local_update(spec_u, w, s, o, xk,
                                                         yk, rk),
                wk, sk, ouk, x, y, lanes.keys(r1, ctx))
            wk, sk, ouk = lanes.keep((wk_n, sk_n, ouk_n), (wk, sk, ouk))

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
        _spec_u, spec_d = self._specs()
        c = state.clients
        wg, sg = state.server.params, state.server.model_state
        odg = state.server.opt_distill
        _r1, r2, _r3, r4 = jax.random.split(rng, 4)
        with jax.named_scope(PREDICT):
            xo = jnp.take(ctx.open_x, ctx.o_idx, axis=0)
        with jax.named_scope(AGGREGATE):
            lanes = self._lanes(ctx)
        with jax.named_scope(DISTILL):
            odk = lanes.take(c.opt_distill)
        wk, sk, ouk, up_loss, probs = inflight

        # 3-5. Upload / Aggregation / Broadcast over all K upload lanes
        with jax.named_scope(AGGREGATE):
            pw, global_logit, sa_entropy = self._teacher(lanes.zeros(probs),
                                                         ctx)
            g_entropy = jnp.mean(entropy(global_logit))

        # 6. Distillation (clients, Eq. 10; absent clients keep their state)
        with jax.named_scope(DISTILL):
            wk_n, sk_n, odk_n, d_loss = over_clients(
                lambda w, s, o, rk: local_distill(spec_d, w, s, o, xo,
                                                  global_logit, rk),
                wk, sk, odk, lanes.keys(r2, ctx))
            wk, sk, odk = lanes.keep((wk_n, sk_n, odk_n), (wk, sk, odk))

        # 6'. server global model (Eq. 11), with its own key r4
        with jax.named_scope(SERVER_DISTILL):
            wg, sg, odg, gd_loss = local_distill(spec_d, wg, sg, odg, xo,
                                                 global_logit, r4)

        with jax.named_scope(DISTILL):
            clients = lanes.put(ClientState(wk, sk, ouk, odk), c)
        metrics = {"update_loss": lanes.mean(up_loss),
                   "distill_loss": lanes.mean(d_loss),
                   "server_distill_loss": gd_loss,
                   "global_entropy": g_entropy,
                   "sa_entropy": sa_entropy}
        if pw is not None:
            # normalized per-client aggregation weights (non-scalar: exposed
            # on `FedEngine.last_metrics`, kept out of the scalar history);
            # pinned total so the diagnostic agrees bitwise across the
            # dense-masked and sparse programs like every other reduction
            metrics["agg_weights"] = pw / jnp.maximum(pinned_sum(pw), 1e-9)
        if lanes.mask is not None:
            metrics["participants"] = jnp.sum(ctx.mask.astype(jnp.float32))
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
        c = state.clients
        lanes = LanePlan.for_round(ctx)
        x, y = lanes.take((ctx.x, ctx.y))
        wk, sk, ok = lanes.take((c.params, c.model_state, c.opt_update))
        tk, owns = jax.vmap(
            lambda w, s, xk, yk: fd_lib.per_label_logits(
                self.apply_fn, w, s, xk, yk, hp.n_classes))(wk, sk, x, y)
        if lanes.mask is not None:
            # absent clients' per-class tables leave the Eq. 5 mean entirely
            owns = jnp.logical_and(owns, lanes.lane_mask.astype(bool)[:, None])
        # lanes not computed join as (zeros, False): the same Eq. 5 terms as
        # the masked round's (finite table, False-by-mask) lanes
        tg, n_own = fd_lib.aggregate_fd(*lanes.zeros((tk, owns)))

        def per_client(w, s, o, xk, yk, tkk, rk):
            tgt = fd_lib.distill_targets(tg, tkk, n_own, yk)
            return local_update(spec, w, s, o, xk, yk, rk,
                                distill_extra=tgt, gamma=hp.gamma)

        wk_n, sk_n, ok_n, losses = jax.vmap(per_client)(
            wk, sk, ok, x, y, tk, lanes.keys(rng, ctx))
        clients = lanes.put(
            ClientState(*lanes.keep((wk_n, sk_n, ok_n), (wk, sk, ok))), c)
        metrics = {"update_loss": lanes.mean(losses),
                   "global_logit": tg}        # (C, C), for Fig. 2 analysis
        return RoundState(clients=clients), metrics

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
        lanes = LanePlan.for_round(ctx)

        def per_client(xk, yk, rk):
            opt_state = spec.opt.init(w0)
            return local_update(spec, w0, s0, opt_state, xk, yk, rk)

        # client state is ephemeral: only the computed lanes train, and the
        # lanes not computed join the Eq. 3 average as exact zeros under an
        # exact-zero weight
        x, y = lanes.take((ctx.x, ctx.y))
        wk, sk, _, losses = jax.vmap(per_client)(x, y, lanes.keys(rng, ctx))
        wk, sk = lanes.zeros((wk, sk))
        weights = (jnp.ones((K,), jnp.float32)
                   if isinstance(ctx.weights, tuple) else ctx.weights)
        if lanes.mask is not None:
            # absent clients carry exactly zero weight in the Eq. 3 average
            # (client state is ephemeral in FedAvg, so masking the average IS
            # the partial-participation round); stale async contributions are
            # discounted FedAsync-style
            weights = participation_weights(
                ctx.mask, ctx.stale if present(ctx.stale) else None,
                self.hp.staleness_decay, base=weights)
        new_w0 = weighted_average(wk, weights)
        new_s0 = weighted_average(sk, weights)
        metrics = {"update_loss": lanes.mean(losses)}
        if lanes.mask is not None:
            metrics["participants"] = jnp.sum(ctx.mask.astype(jnp.float32))
        return RoundState(server=ServerState(new_w0, new_s0)), metrics

    def upload_payload(self, state: RoundState, ctx: BatchCtx):
        """One client's upload: the full parameter vector (+ model state)."""
        return {"params": state.server.params,
                "model_state": state.server.model_state}

    def eval_params(self, state: RoundState):
        return state.server.params, state.server.model_state
