"""DS-FL engine (paper Algorithm 1) at "paper scale": K clients simulated as
a vmapped leading axis of stacked parameter pytrees; the server's aggregation
is a mean over that axis (on a TPU mesh this axis is sharded over pods and
the mean lowers to the logit all-reduce — see core/llm_dsfl.py).

Round structure (Fig. 1 (c)):
  1. Update       - local SGD on private data (vmap of client.local_update)
  2. Prediction   - local probs on the shared open-batch o_r (Eq. 9)
  3-5. Upload/Aggregate/Broadcast - aggregation.aggregate (SA / ERA)
  6. Distillation - clients AND the server global model train on (D^{o_r}, T̂)
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from ..optim import optimizers as opt_lib
from .aggregation import aggregate
from .client import LocalSpec, local_distill, local_update, predict_probs
from .losses import accuracy, entropy


@dataclass(frozen=True)
class DSFLConfig:
    rounds: int = 30
    local_epochs: int = 5
    distill_epochs: int = 5
    batch_size: int = 100
    open_batch: int = 1000          # |o_r|
    lr: float = 0.1
    lr_distill: float = 0.1
    optimizer: str = "sgd"
    aggregation: str = "era"        # sa | era | weighted_era
    temperature: float = 0.1        # ERA softmax temperature
    staleness_decay: float = 0.5    # async: weight factor per round of lag
    seed: int = 0


def make_dsfl_round(apply_fn: Callable, hp: DSFLConfig,
                    corrupt: Optional[Callable] = None):
    """Build the jittable one-round function.

    corrupt(probs (K, n, C), rng) -> probs lets attack experiments inject
    malicious local logits between "2. Prediction" and "4. Aggregation"."""
    opt_u = opt_lib.make(hp.optimizer, hp.lr)
    opt_d = opt_lib.make(hp.optimizer, hp.lr_distill)
    spec_u = LocalSpec(apply_fn, opt_u, hp.local_epochs, hp.batch_size)
    spec_d = LocalSpec(apply_fn, opt_d, hp.distill_epochs,
                       min(hp.batch_size, hp.open_batch))

    def round_fn(wk, sk, ouk, odk, wg, sg, odg, x, y, open_x, o_idx, rng):
        K = x.shape[0]
        r1, r2, r3, r4 = jax.random.split(rng, 4)
        xo = jnp.take(open_x, o_idx, axis=0)

        # 1. Update
        wk, sk, ouk, up_loss = jax.vmap(
            lambda w, s, o, xk, yk, rk: local_update(spec_u, w, s, o, xk, yk, rk)
        )(wk, sk, ouk, x, y, jax.random.split(r1, K))

        # 2. Prediction (local logits on o_r)
        probs = jax.vmap(lambda w, s: predict_probs(apply_fn, w, s, xo))(wk, sk)
        if corrupt is not None:
            probs = corrupt(probs, xo, r3)

        # 3-5. Upload / Aggregation / Broadcast
        global_logit = aggregate(probs, hp.aggregation, hp.temperature)
        sa_entropy = jnp.mean(entropy(jnp.mean(probs, axis=0)))
        g_entropy = jnp.mean(entropy(global_logit))

        # 6. Distillation (clients, Eq. 10)
        wk, sk, odk, d_loss = jax.vmap(
            lambda w, s, o, rk: local_distill(spec_d, w, s, o, xo,
                                              global_logit, rk)
        )(wk, sk, odk, jax.random.split(r2, K))

        # 6'. server global model (Eq. 11) — own key, so the server's distill
        # minibatch permutations are independent of the clients' (r2)
        wg, sg, odg, gd_loss = local_distill(spec_d, wg, sg, odg, xo,
                                             global_logit, r4)

        metrics = {"update_loss": jnp.mean(up_loss),
                   "distill_loss": jnp.mean(d_loss),
                   "server_distill_loss": gd_loss,
                   "global_entropy": g_entropy,
                   "sa_entropy": sa_entropy}
        return (wk, sk, ouk, odk, wg, sg, odg), metrics

    return round_fn


@dataclass
class DSFLEngine:
    """Python-level orchestration: round jitting, o_r sampling, eval, history.

    .. deprecated:: use ``repro.core.engine.FedEngine`` with
       ``repro.core.algorithms.DSFLAlgorithm`` — the algorithm-agnostic
       trainer that also runs FD and FedAvg.  This class is kept as the
       golden reference for the parity test and for old callers."""
    apply_fn: Callable
    hp: DSFLConfig
    eval_fn: Callable                      # (w, s) -> dict of metrics
    corrupt: Optional[Callable] = None
    history: list = field(default_factory=list)

    def __post_init__(self):
        self._round = jax.jit(make_dsfl_round(self.apply_fn, self.hp,
                                              self.corrupt))

    def init_states(self, wk, sk, wg, sg):
        opt_u = opt_lib.make(self.hp.optimizer, self.hp.lr)
        opt_d = opt_lib.make(self.hp.optimizer, self.hp.lr_distill)
        ouk = jax.vmap(opt_u.init)(wk)
        odk = jax.vmap(opt_d.init)(wk)
        odg = opt_d.init(wg)
        return ouk, odk, odg

    def run(self, wk, sk, wg, sg, x, y, open_x, log_every: int = 1):
        hp = self.hp
        rng = jax.random.PRNGKey(hp.seed)
        ouk, odk, odg = self.init_states(wk, sk, wg, sg)
        n_open = open_x.shape[0]
        for r in range(hp.rounds):
            rng, rk, ri = jax.random.split(rng, 3)
            o_idx = jax.random.choice(ri, n_open,
                                      (min(hp.open_batch, n_open),),
                                      replace=False)
            (wk, sk, ouk, odk, wg, sg, odg), m = self._round(
                wk, sk, ouk, odk, wg, sg, odg, x, y, open_x, o_idx, rk)
            if (r + 1) % log_every == 0:
                rec = {"round": r + 1,
                       **{k: float(v) for k, v in m.items()},
                       **self.eval_fn(wg, sg)}
                self.history.append(rec)
        return wk, sk, wg, sg


EVAL = "fed_eval"    # `jax.named_scope` of the test-set forward


def make_eval_fn(apply_fn, x_test, y_test, batch: int = 1000):
    @jax.jit
    def _logits(w, s):
        with jax.named_scope(EVAL):
            logits, _ = apply_fn(w, s, x_test, False)
        return logits

    def eval_fn(w, s):
        logits = _logits(w, s)
        return {"test_acc": float(accuracy(logits, y_test))}

    return eval_fn
