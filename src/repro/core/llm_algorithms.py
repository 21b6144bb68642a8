"""Pod-scale LLM DS-FL / FedAvg on the unified `FedAlgorithm` API.

`LLMDSFLAlgorithm` wraps `llm_dsfl.dsfl_round_step` (and `LLMFedAvgAlgorithm`
its `fedavg_round_step` benchmark twin) behind the same two-method surface
the smallnet algorithms use, so the sharded LLM path shares `FedEngine`:
typed `RoundState` holding the pod-stacked parameters, `BatchCtx` carrying
the private token stacks plus the shared open set (sub-sampled per round via
``o_idx``), msgpack checkpointing, measured wire bytes through the top-k
codec, and engine-side jit.

Each algorithm additionally exposes ``shardings(mesh, state, ctx)`` returning
(state, ctx) sharding pytrees built from `launch.sharding`'s name-based rules
with the federated-client axis on "pod" — `FedEngine(algo, mesh=...)` feeds
them to ``jax.jit(in_shardings=...)`` (with the state donated when
``donate_state=True``), which is exactly the placement the multi-pod dry-run
lowers.  On meshes without a "pod" axis the client axis stays replicated.

The wrappers are pinned bit-for-bit against the raw round steps in
tests/test_llm_algorithms.py, the LLM analogue of `tests/test_engine.py`'s
golden parity against `protocol.DSFLEngine`.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..models.base import ModelConfig
from .aggregation import participation_weights
from .algorithms import BatchCtx, ClientState, EMPTY, RoundState, present
from .llm_dsfl import (LLMDsflHP, dsfl_exchange, dsfl_round_finish,
                       dsfl_round_step, fedavg_round_step,
                       predict_open_probs)


def _participation(ctx: BatchCtx, decay: float) -> dict:
    """The round steps' participation arguments from the sim's ctx fields:
    (K,) aggregation weights (None for the exact full-participation path;
    the aggregation helper's all-zero fallback, decay 0 + all-stale cohort
    -> raw mask), the mask, and the static budget."""
    mask = ctx.mask if present(ctx.mask) else None
    weights = None if mask is None else participation_weights(
        mask, ctx.stale if present(ctx.stale) else None, decay)
    return dict(weights=weights, mask=mask, active_budget=ctx.active_budget)


def _take_open(open_x, o_idx):
    """Gather this round's open batch o_r out of the full shared open set."""
    return jax.tree.map(lambda a: jnp.take(a, o_idx, axis=0), open_x)


def _first_client(tree):
    return jax.tree.map(lambda a: a[0], tree)


def _mean_clients(tree):
    return jax.tree.map(
        lambda a: jnp.mean(a.astype(jnp.float32), axis=0).astype(a.dtype),
        tree)


def _stack_init(model_init, rng, data):
    K = jax.tree.leaves(data.x_clients)[0].shape[0]
    return jax.vmap(model_init)(jax.random.split(rng, K))


def _shardings(cfg: ModelConfig, mesh, state: RoundState, ctx: BatchCtx,
               with_open: bool):
    """(state, ctx) sharding pytrees: params P("pod", <tp/fsdp rules>),
    private batches P("pod", "data", ...), open set data-sharded, indices and
    the round key replicated."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from ..launch.sharding import batch_specs, param_specs, to_named

    client_axis = "pod" if "pod" in mesh.axis_names else None
    pshard = to_named(mesh, param_specs(cfg, state.clients.params, mesh,
                                        client_axis=client_axis))
    st = RoundState(clients=ClientState(params=pshard))
    xsh = to_named(mesh, batch_specs(ctx.x, mesh, client_axis=client_axis))
    rep = NamedSharding(mesh, P())
    # the sim's participation fields (tiny (K,) vectors) stay replicated;
    # mirrored only when present so the ctx treedefs match
    mask = rep if not isinstance(ctx.mask, tuple) else EMPTY
    stale = rep if not isinstance(ctx.stale, tuple) else EMPTY
    # active_budget is pytree *metadata*: it must mirror the real ctx's
    # value or the sharding pytree's treedef won't match the argument's
    budget = ctx.active_budget
    if with_open:
        osh = to_named(mesh, batch_specs(ctx.open_x, mesh))
        return st, BatchCtx(x=xsh, open_x=osh, o_idx=rep, mask=mask,
                            stale=stale, active_budget=budget)
    return st, BatchCtx(x=xsh, mask=mask, stale=stale, active_budget=budget)


@dataclass(frozen=True)
class LLMDSFLAlgorithm:
    """DS-FL at pod scale on the unified API: each federated client is one
    pod; the round's only cross-pod collective is the open-batch logit
    exchange (all-gather of top-k pairs under ``hp.topk``).

    ``probe_rows`` (flat indices, sequence * S + position, of tokens of
    the round's open batch o_r) makes every round also report what the
    exchange delivered there: the metric ``client_mean``, the clients'
    float32 mean prediction ERA sharpened, (n, V), on the engine's
    ``last_metrics``.  None (the default) reports nothing and leaves the
    round's program as it was."""
    cfg: ModelConfig
    hp: LLMDsflHP
    probe_rows: tuple | None = None

    name = "llm_dsfl"
    uses_open = True

    def init(self, rng, model_init, data) -> RoundState:
        return self.init_from(_stack_init(model_init, rng, data))

    def init_from(self, stacked_params) -> RoundState:
        """Build a RoundState around externally-initialized pod-stacked
        params (leaves (n_clients, ...))."""
        return RoundState(clients=ClientState(params=stacked_params))

    def round(self, state: RoundState, ctx: BatchCtx, rng):
        del rng   # dsfl_round_step is deterministic given the batches
        open_b = _take_open(ctx.open_x, ctx.o_idx)
        return self._result(*dsfl_round_step(
            self.cfg, state.clients.params, ctx.x, open_b, self.hp,
            probe_rows=self.probe_rows,
            **_participation(ctx, self.hp.staleness_decay)))

    @staticmethod
    def _result(new, loss, *probe):
        metrics = {"loss": loss}
        if probe:
            metrics["client_mean"] = probe[0]
        return RoundState(clients=ClientState(params=new)), metrics

    # -- pipelined round halves (engine `overlap=True` path) ----------------
    # round == round_finish(state, ctx, round_start(state, ctx, rng), rng)
    # bitwise: the halves are the same ops in the same order, just split at
    # the wire boundary so the scan body can issue round r+1's exchange
    # before round r's compute leg retires.
    def round_start(self, state: RoundState, ctx: BatchCtx, rng):
        """Issue the round's WIRE leg: open-batch prediction + the cross-pod
        all-gather of the (compressed) uploads.  Returns the in-flight
        exchange buffers; depends only on the round's input params."""
        del rng   # dsfl_round_step is deterministic given the batches
        open_b = _take_open(ctx.open_x, ctx.o_idx)
        return dsfl_exchange(
            self.cfg, state.clients.params, open_b, self.hp,
            **_participation(ctx, self.hp.staleness_decay))

    def round_finish(self, state: RoundState, ctx: BatchCtx, inflight, rng):
        """Consume the in-flight exchange: aggregate the teacher and run the
        hybrid CE+KD client step (the leg whose private-data branch never
        touches ``inflight`` — the slack the wire hides behind)."""
        del rng
        open_b = _take_open(ctx.open_x, ctx.o_idx)
        return self._result(*dsfl_round_finish(
            self.cfg, state.clients.params, ctx.x, open_b, inflight, self.hp,
            probe_rows=self.probe_rows,
            **_participation(ctx, self.hp.staleness_decay)))

    def upload_payload(self, state: RoundState, ctx: BatchCtx):
        """One client's upload: per-token class distributions on o_r —
        (|o_r|, S, V) bf16, the tensor the wire codec encodes."""
        open_b = _take_open(ctx.open_x, ctx.o_idx)
        return predict_open_probs(self.cfg, _first_client(state.clients.params),
                                  open_b)

    def eval_params(self, state: RoundState):
        # no server model at LLM scale: score the mean client model (cf. FD)
        return _mean_clients(state.clients.params), EMPTY

    def shardings(self, mesh, state: RoundState, ctx: BatchCtx):
        return _shardings(self.cfg, mesh, state, ctx, with_open=True)


@dataclass(frozen=True)
class LLMFedAvgHP:
    lr: float = 1e-4
    staleness_decay: float = 0.5    # async sim: weight factor per round of lag
    rounds: int = 10
    seed: int = 0


@dataclass(frozen=True)
class LLMFedAvgAlgorithm:
    """Benchmark 1 at pod scale: local SGD then a parameter mean over the pod
    axis — the all-reduce whose bytes equal the model size."""
    cfg: ModelConfig
    hp: LLMFedAvgHP

    name = "llm_fedavg"
    uses_open = False

    def init(self, rng, model_init, data) -> RoundState:
        return self.init_from(_stack_init(model_init, rng, data))

    def init_from(self, stacked_params) -> RoundState:
        return RoundState(clients=ClientState(params=stacked_params))

    def round(self, state: RoundState, ctx: BatchCtx, rng):
        del rng
        new, loss = fedavg_round_step(
            self.cfg, state.clients.params, ctx.x, self.hp.lr,
            **_participation(ctx, self.hp.staleness_decay))
        return RoundState(clients=ClientState(params=new)), {"loss": loss}

    def upload_payload(self, state: RoundState, ctx: BatchCtx):
        """One client's upload: its full parameter pytree."""
        return _first_client(state.clients.params)

    def eval_params(self, state: RoundState):
        # clients are synced by the round's broadcast: any one of them
        return _first_client(state.clients.params), EMPTY

    def shardings(self, mesh, state: RoundState, ctx: BatchCtx):
        return _shardings(self.cfg, mesh, state, ctx, with_open=False)
