"""DS-FL at pod scale: each federated client is one pod of the production
mesh.  Client-stacked parameters (n_clients, ...) are sharded P("pod", ...),
so the ONLY cross-pod collective in a DS-FL round is the open-batch logit
mean inside ``aggregate`` — the paper's communication claim, visible directly
as all-reduce bytes in the compiled HLO (vs. FedAvg's parameter all-reduce).

Step functions here are mesh-agnostic pure JAX; launch/ assigns shardings.

Note: the bespoke per-step training loop that used to drive these functions
directly (launch/train.py's LLM branch) is retired — new code runs them
through `core.llm_algorithms.LLMDSFLAlgorithm` / `LLMFedAvgAlgorithm` on the
unified `FedEngine`.  The round-step functions below stay as the reference
implementations the algorithm wrappers are pinned against bit-for-bit
(tests/test_llm_algorithms.py), mirroring how `protocol.DSFLEngine` backs
`DSFLAlgorithm`.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..models.api import model_logits
from ..models.base import ModelConfig
from .aggregation import era, sa, topk_compress, weighted_era, weighted_sa
from .hierarchy import hierarchical_weighted_era, hierarchical_weighted_sa
from .algorithms import (AGGREGATE, DISTILL, PREDICT, UPDATE, active_indices,
                         gather_clients, masked_mean, scatter_clients,
                         scatter_zeros, select_clients)
from .losses import (HIGHEST, distill_xent, pinned_sum, topk_distill_xent,
                     xent_int_labels)


@dataclass(frozen=True)
class LLMDsflHP:
    lr: float = 1e-4
    gamma: float = 1.0              # weight of the distillation term
    temperature: float = 0.1        # ERA
    aggregation: str = "era"        # sa | era
    agg_edges: int = 1              # two-level ERA tree width (core.hierarchy)
    aux_weight: float = 0.01        # MoE load-balance loss
    topk: int | None = None         # sparsified logit exchange (beyond paper)
    microbatches: int = 1           # gradient accumulation (activation peak /m)
    staleness_decay: float = 0.5    # async sim: weight factor per round of lag
    # engine-facing fields (`FedEngine` reads rounds/seed/open_batch; the
    # round-step functions above ignore them)
    rounds: int = 10
    seed: int = 0
    open_batch: int = 8             # |o_r| in sequences per round


# ------------------------------------------------------------ plain steps ----
def lm_loss(cfg: ModelConfig, params, batch, aux_weight: float = 0.01):
    """Next-token CE (+ MoE aux).  labels = tokens shifted left."""
    logits, aux = model_logits(cfg, params, batch)
    labels = jnp.concatenate([batch["tokens"][:, 1:],
                              batch["tokens"][:, -1:]], axis=1)
    return xent_int_labels(logits, labels) + aux_weight * aux


def sgd_train_step(cfg: ModelConfig, params, batch, lr: float,
                   aux_weight: float = 0.01):
    """Benchmark local step ("1. Update" at LLM scale).  Plain SGD is the
    paper-faithful optimizer; large-model memory fits without moments."""
    loss, grads = jax.value_and_grad(
        lambda p: lm_loss(cfg, p, batch, aux_weight))(params)
    new = jax.tree.map(lambda p, g: p - (lr * g).astype(p.dtype), params, grads)
    return new, loss


# ------------------------------------------------------- DS-FL hybrid step ---
def dsfl_client_loss(cfg: ModelConfig, params, private_batch, open_batch,
                     teacher, hp: LLMDsflHP):
    """CE on private tokens + gamma * KD on the open batch (Eqs. 1 + 10 fused
    into one local step — the per-round client compute of DS-FL).  The CE
    term is the round's update step and the KD term its distillation
    (named scopes ``dsfl_update`` / ``dsfl_distill``, which the gradient
    carries into the backward pass); the round has no server model, so no
    ``dsfl_server_distill``."""
    with jax.named_scope(UPDATE):
        ce = lm_loss(cfg, params, private_batch, hp.aux_weight)
    with jax.named_scope(DISTILL):
        logits_o, _ = model_logits(cfg, params, open_batch)
        if hp.topk is not None:
            tv, ti = teacher
            kd = topk_distill_xent(logits_o, tv, ti)
        else:
            kd = distill_xent(logits_o, teacher)
    return ce + hp.gamma * kd


def _split_mb(tree, m: int):
    return jax.tree.map(
        lambda x: x.reshape((m, x.shape[0] // m) + x.shape[1:]), tree)


def dsfl_client_step(cfg: ModelConfig, params, private_batch, open_batch,
                     teacher, hp: LLMDsflHP):
    if hp.microbatches <= 1:
        loss, grads = jax.value_and_grad(
            lambda p: dsfl_client_loss(cfg, p, private_batch, open_batch,
                                       teacher, hp))(params)
    else:
        # gradient accumulation: scan over microbatches, fp32 accumulators
        m = hp.microbatches
        mbs = (_split_mb(private_batch, m), _split_mb(open_batch, m),
               _split_mb(teacher, m))

        def body(acc, mb):
            g_acc, l_acc = acc
            pb, ob, tb = mb
            l, g = jax.value_and_grad(
                lambda p: dsfl_client_loss(cfg, p, pb, ob, tb, hp))(params)
            g_acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32) / m,
                                 g_acc, g)
            return (g_acc, l_acc + l / m), None

        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (grads, loss), _ = jax.lax.scan(body, (g0, jnp.float32(0.0)), mbs)
    with jax.named_scope(UPDATE):
        new = jax.tree.map(lambda p, g: p - (hp.lr * g).astype(p.dtype),
                           params, grads)
    return new, loss


# ----------------------------------------------------------- round step ------
def predict_open_probs(cfg: ModelConfig, params, open_batch):
    """"2. Prediction": per-token class distribution on the open batch."""
    logits, _ = model_logits(cfg, params, open_batch)
    return jax.nn.softmax(logits.astype(jnp.float32), axis=-1
                          ).astype(jnp.bfloat16)


def _is_sparse_round(K: int, hp: LLMDsflHP, weights, active_budget) -> bool:
    """The (static, trace-time) predicate routing a round through the
    participation-sparse gather plane.  Shared by the exchange and finish
    halves so a split round can never disagree with the fused one about
    which plane it is on."""
    return (weights is not None and active_budget is not None
            and active_budget < K and hp.topk is None)


@jax.named_scope(PREDICT)
def dsfl_exchange(cfg: ModelConfig, stacked_params, open_batch,
                  hp: LLMDsflHP, weights=None, mask=None,
                  active_budget=None):
    """The WIRE leg of a DS-FL round: "2. Prediction" + "3. Upload".

    Everything in the round up to (and including) the cross-pod
    all-gather, and nothing after it: clients predict on the shared open
    batch and their uploads leave the pod.  Returns the in-flight
    exchange buffers `dsfl_round_finish` consumes —

      * ``hp.topk``: the pod-gathered ``(values, indices)`` pair — the
        (K, B, S, k) compressed uploads after the explicit shard_map
        all-gather (k*(4+4) bytes/token of inter-pod traffic);
      * dense: the full (K, B, S, V) probability stack;
      * participation-sparse: the (m, B, S, V) active-lane stack (the
        finish leg scatters it into exact zeros).

    Splitting here is what lets the engine's pipelined scan issue round
    r's all-gather before round r's compute leg: the buffers returned
    here depend only on the round's *input* params, while most of the
    finish leg (the private-data CE branch of the hybrid client step)
    never touches them — so a latency-hiding scheduler can overlap the
    gather with that compute without changing a single op.  The split is
    pure restructuring: ``dsfl_round_step`` is literally
    ``dsfl_round_finish(..., dsfl_exchange(...))``, so fused and split
    rounds are the same jaxpr and the parity pins stay bitwise."""
    K = jax.tree.leaves(stacked_params)[0].shape[0]
    if _is_sparse_round(K, hp, weights, active_budget):
        act = weights if mask is None else mask
        idx = active_indices(act, active_budget)
        params_m = gather_clients(stacked_params, idx)
        probs_m = jax.vmap(lambda p: predict_open_probs(cfg, p, open_batch)
                           )(params_m)                      # (m, B, S, V)
        return (probs_m,)
    probs = jax.vmap(lambda p: predict_open_probs(cfg, p, open_batch)
                     )(stacked_params)                     # (Kc, B, S, V)
    if hp.topk is not None:
        tv, ti = jax.vmap(lambda pr: topk_compress(pr, hp.topk))(probs)
        # force pod-replication of the SMALL uploads (the all-gather is the
        # exchange); densification and ERA then run without dense collectives
        # The exchange leg as an EXPLICIT collective: left to GSPMD, the
        # partitioner moves the pod-replication point after densification
        # and all-gathers the dense teacher (measured: 10 GB cross-pod).
        # A pod-axis shard_map pins the all-gather on the (value, index)
        # pairs — k*(4+4) bytes/token of inter-pod traffic.
        mesh = jax.sharding.get_abstract_mesh()
        if "pod" in mesh.axis_names:
            from jax.sharding import PartitionSpec as P
            sm = jax.shard_map(
                lambda v, i: (jax.lax.all_gather(v[0], "pod"),
                              jax.lax.all_gather(i[0], "pod")),
                mesh=mesh,
                in_specs=(P("pod"), P("pod")),
                out_specs=(P(), P()),
                axis_names={"pod"})
            tv, ti = sm(tv, ti)
        return (tv, ti)
    return (probs,)


def dsfl_round_finish(cfg: ModelConfig, stacked_params, private_batches,
                      open_batch, inflight, hp: LLMDsflHP, weights=None,
                      mask=None, active_budget=None):
    """The COMPUTE leg of a DS-FL round: "4. Aggregation" + "5. Broadcast"
    + the hybrid CE+KD client step, consuming the exchange buffers
    `dsfl_exchange` put in flight.  The private-batch CE branch of
    ``dsfl_client_step`` has no data dependency on ``inflight`` — only
    the KD term and the open-branch backward seed do — which is the slack
    the pipelined schedule hides the wire behind."""
    from ..models.shardctx import constrain
    K = jax.tree.leaves(stacked_params)[0].shape[0]
    if _is_sparse_round(K, hp, weights, active_budget):
        return _dsfl_finish_sparse(cfg, stacked_params, private_batches,
                                   open_batch, inflight, hp, weights, mask,
                                   active_budget)
    if hp.topk is not None:
        tv, ti = inflight
        with jax.named_scope(AGGREGATE):
            # shard-local densify: iota-compare instead of scatter (a
            # scatter into a vocab-sharded output would replicate the dense
            # tensor)
            V = cfg.eff_vocab   # probs carry the padded (TP-divisible) vocab
            iota = jax.lax.broadcasted_iota(jnp.int32, (1, 1, 1, 1, V), 4)
            # (Kc, B, S, k, V)
            onehot = (iota == ti[..., None]).astype(jnp.float32)
            dense = jnp.einsum("cbsk,cbskv->cbsv", tv.astype(jnp.float32),
                               onehot, precision=HIGHEST)
            dense = constrain(dense, None, "batch", None, "model")
            teacher = aggregate_teacher(dense, hp, weights)
            teacher = constrain(teacher, "batch", None, "model")
        # the exchange leg is compressed; the pod-local distillation uses the
        # dense (vocab-sharded) teacher — no top_k over a sharded axis
        import dataclasses
        hp = dataclasses.replace(hp, topk=None)
    else:
        (probs,) = inflight
        with jax.named_scope(AGGREGATE):
            teacher = aggregate_teacher(probs, hp, weights)

    new_params, losses = jax.vmap(
        lambda p, b: dsfl_client_step(cfg, p, b, open_batch, teacher, hp)
    )(stacked_params, private_batches)
    if weights is not None:
        # absent clients neither update nor average into the loss
        m = (weights if mask is None else mask).astype(jnp.float32) > 0
        with jax.named_scope(UPDATE):
            new_params = select_clients(m, new_params, stacked_params)
        return new_params, masked_mean(losses, m)
    return new_params, jnp.mean(losses)


def dsfl_round_step(cfg: ModelConfig, stacked_params, private_batches,
                    open_batch, hp: LLMDsflHP, weights=None, mask=None,
                    active_budget=None):
    """One full DS-FL round over the pod-sharded client axis: the
    composition ``dsfl_round_finish(..., dsfl_exchange(...))``.

    stacked_params: pytree with leading (n_clients,) axis, sharded P("pod",.).
    private_batches: each leaf (n_clients, B, ...).  open_batch: (B, ...) —
    identical on every pod (the shared open set).

    The mean over axis 0 inside sa/era is the ONLY cross-pod collective.
    With hp.topk, clients compress their logits BEFORE the exchange (the
    paper's upload leg): the cross-pod traffic becomes an all-gather of
    (value, index) pairs — k*(4+4) bytes/token instead of V*2 — and the
    dense densify+ERA runs pod-locally on the gathered pairs.

    ``weights`` (K,), when given, turns the exchange into the sim layer's
    partial-participation round: zero-weight (absent) clients contribute
    nothing to the aggregate and keep their parameters; stale-decayed
    weights discount async contributions.  ``mask`` (K,) separately names
    the participants — a stale participant whose aggregation weight
    decayed to exactly zero still trains and averages into the loss, same
    as the core `algorithms` path.  ``None`` (the default) is the exact
    full-participation path the parity tests pin bit-for-bit.

    ``active_budget=m`` (with ``weights``) runs the participation-sparse
    round: prediction and the hybrid client step execute on only the m
    gathered active lanes of the pod-sharded stack, and the gathered
    uploads scatter into exact zeros before the weighted exchange — a
    ~K/m client-compute reduction, bitwise identical to the dense
    ``weights=`` round.  The top-k exchange keeps the dense path (its
    pinned pod-axis all-gather is shaped by the full client axis).
    """
    inflight = dsfl_exchange(cfg, stacked_params, open_batch, hp,
                             weights=weights, mask=mask,
                             active_budget=active_budget)
    return dsfl_round_finish(cfg, stacked_params, private_batches,
                             open_batch, inflight, hp, weights=weights,
                             mask=mask, active_budget=active_budget)


def _dsfl_finish_sparse(cfg: ModelConfig, stacked_params, private_batches,
                        open_batch, inflight, hp: LLMDsflHP, weights, mask,
                        active_budget: int):
    """Participation-sparse finish leg: same gather -> compute -> scatter
    plane as `algorithms.DSFLAlgorithm._sparse_round`, along the
    pod-sharded client axis.  Bitwise identical to the dense ``weights=``
    round (tests/test_llm_dsfl.py): active lanes see the same per-client
    math, and the scattered zero lanes multiply against the same
    exact-zero aggregation weights the dense stack's lanes do.  ``idx``
    is re-derived from the ctx (a pure, cheap argsort) rather than
    carried in ``inflight``, so the exchange buffers stay O(m)."""
    K = jax.tree.leaves(stacked_params)[0].shape[0]
    act = weights if mask is None else mask
    with jax.named_scope(UPDATE):
        idx = active_indices(act, active_budget)
        act_m = jnp.take(act, idx, axis=0)
        params_m = gather_clients(stacked_params, idx)
        batches_m = gather_clients(private_batches, idx)

    (probs_m,) = inflight                                   # (m, B, S, V)
    with jax.named_scope(AGGREGATE):
        teacher = aggregate_teacher(scatter_zeros(probs_m, K, idx), hp,
                                    weights)

    new_m, losses_m = jax.vmap(
        lambda p, b: dsfl_client_step(cfg, p, b, open_batch, teacher, hp)
    )(params_m, batches_m)
    with jax.named_scope(UPDATE):
        new_m = select_clients(act_m.astype(jnp.float32) > 0, new_m,
                               params_m)
        new_params = scatter_clients(new_m, stacked_params, idx)
    losses = scatter_zeros(losses_m, K, idx)
    return new_params, masked_mean(losses, act.astype(jnp.float32) > 0)


def aggregate_teacher(probs, hp: LLMDsflHP, weights):
    """sa/era over the client axis; the weighted variants zero out absent
    clients and decay stale ones when the sim supplies ``weights``.
    ``hp.agg_edges > 1`` reduces the client axis through the two-level
    edge -> server tree (`core.hierarchy`) — on a pod-sharded client axis
    each edge's partial sum is shard-local, so the cross-pod exchange
    carries n_edges (n, S, V) partials instead of K upload stacks.  The
    parity/tolerance contract is `core.hierarchy`'s: bitwise at one edge,
    pinned tolerance deeper."""
    if hp.agg_edges > 1:
        w = (jnp.ones((probs.shape[0],), jnp.float32)
             if weights is None else weights)
        agg = (hierarchical_weighted_era(probs, w, hp.temperature,
                                         hp.agg_edges)
               if hp.aggregation == "era"
               else hierarchical_weighted_sa(probs, w, hp.agg_edges))
    elif weights is None:
        agg = era(probs, hp.temperature) if hp.aggregation == "era" \
            else sa(probs)
    else:
        agg = (weighted_era(probs, weights, hp.temperature)
               if hp.aggregation == "era" else weighted_sa(probs, weights))
    return agg.astype(jnp.bfloat16)


def fedavg_round_step(cfg: ModelConfig, stacked_params, private_batches,
                      lr: float, weights=None, mask=None,
                      active_budget=None):
    """Benchmark 1 at pod scale: local step then parameter mean over the pod
    axis — its all-reduce bytes = model size (the paper's comparison).

    ``weights`` (K,), when given, makes the mean a weighted average (zero
    for absent clients, staleness-decayed for async ones; client state is
    ephemeral in FedAvg, so masking the average is the whole
    partial-participation round); ``mask`` (K,) names the participants
    whose losses average into the metric even if their weight decayed to
    zero.  ``None`` is the exact pinned path.

    ``active_budget=m`` (with ``weights``) gathers the m active lanes,
    trains only those, and scatters into exact zeros — the Eq. 3 weighted
    mean multiplies the zero lanes by the same exact-zero weights the
    dense round's lanes get, so the result is bitwise identical."""
    K = jax.tree.leaves(stacked_params)[0].shape[0]
    if (weights is not None and active_budget is not None
            and active_budget < K):
        act = weights if mask is None else mask
        idx = active_indices(act, active_budget)
        new_m, losses_m = jax.vmap(
            lambda p, b: sgd_train_step(cfg, p, b, lr)
        )(gather_clients(stacked_params, idx),
          gather_clients(private_batches, idx))
        new_params = jax.tree.map(lambda a: scatter_zeros(a, K, idx), new_m)
        losses = scatter_zeros(losses_m, K, idx)
    else:
        new_params, losses = jax.vmap(
            lambda p, b: sgd_train_step(cfg, p, b, lr))(stacked_params,
                                                        private_batches)
    if weights is None:
        avg = jax.tree.map(
            lambda leaf: jnp.mean(leaf.astype(jnp.float32), axis=0,
                                  keepdims=True).astype(leaf.dtype),
            new_params)
        loss = jnp.mean(losses)
    else:
        w = weights.astype(jnp.float32)
        # dot-lowered total: bitwise-stable across the dense/sparse programs
        w = w / jnp.maximum(pinned_sum(w), 1e-9)
        avg = jax.tree.map(
            lambda leaf: jnp.einsum("k,k...->...", w,
                                    leaf.astype(jnp.float32),
                                    precision=HIGHEST
                                    )[None].astype(leaf.dtype), new_params)
        m = (weights if mask is None else mask).astype(jnp.float32) > 0
        loss = masked_mean(losses, m)
    broad = jax.tree.map(lambda a, ref: jnp.broadcast_to(a, ref.shape),
                         avg, new_params)
    return broad, loss
