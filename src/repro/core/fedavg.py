"""Benchmark 1: Federated Averaging (McMahan et al., AISTATS'17; paper §2.1).

One round: broadcast w0 -> E local epochs per client -> size-weighted
parameter average (Eq. 3).  BatchNorm running statistics are averaged like
any other leaf (standard FedAvg behaviour)."""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from .client import LocalSpec, local_update
from .losses import HIGHEST, pinned_sum


def weighted_average(stacked, weights: jax.Array):
    """Eq. 3: sum_k (I_k / I) w_k over the leading client axis.  The weight
    total is dot-lowered (`losses.pinned_sum`) so the normalization — and
    with it the whole average — is bitwise identical between the dense
    masked and participation-sparse round programs."""
    w = weights.astype(jnp.float32)
    w = w / pinned_sum(w)

    def avg(leaf):
        return jnp.einsum("k,k...->...", w, leaf.astype(jnp.float32),
                          precision=HIGHEST).astype(leaf.dtype)

    return jax.tree.map(avg, stacked)


def make_fedavg_round(spec: LocalSpec):
    """Returns a jitted round: (w0, s0, data, weights, rng) -> (w0', s0', loss).
    Malicious clients (model poisoning) are injected by the caller via the
    ``override`` hook on the stacked client params.

    .. deprecated:: prefer ``algorithms.FedAvgAlgorithm`` under
       ``engine.FedEngine`` (same math, unified API)."""

    def round_fn(w0, s0, x, y, weights, rng, override=None):
        K = x.shape[0]
        rngs = jax.random.split(rng, K)

        def per_client(xk, yk, rk):
            opt_state = spec.opt.init(w0)
            return local_update(spec, w0, s0, opt_state, xk, yk, rk)[:2]

        wk, sk = jax.vmap(per_client)(x, y, rngs)
        if override is not None:                     # (mask (K,), params (K,...))
            mask, forced = override
            pick = lambda a, b: jnp.where(
                mask.reshape((K,) + (1,) * (a.ndim - 1)), b.astype(a.dtype), a)
            wk = jax.tree.map(pick, wk, forced)
        new_w0 = weighted_average(wk, weights)
        new_s0 = weighted_average(sk, weights)
        return new_w0, new_s0

    return round_fn


def make_fedavg_engine(spec: LocalSpec, eval_fn: Callable):
    round_fn = jax.jit(make_fedavg_round(spec), static_argnames=())

    def run(w0, s0, x, y, weights, rounds: int, rng, log_every: int = 1,
            history=None):
        history = history if history is not None else []
        for r in range(rounds):
            rng, rk = jax.random.split(rng)
            w0, s0 = round_fn(w0, s0, x, y, weights, rk)
            if (r + 1) % log_every == 0:
                history.append({"round": r + 1, **eval_fn(w0, s0)})
        return w0, s0, history

    return run
