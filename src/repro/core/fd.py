"""Benchmark 2: Federated Distillation (Jeong et al. 2018; paper §2.2).

Clients exchange *per-class average* probability vectors instead of
per-sample logits:

  Eq. 4: t_{k,n} = mean of F(d|w_k) over client k's samples with label n
  Eq. 5: t_{g,n} = mean over clients that own class n
  Eq. 6: per-sample distill target debiases the client's own contribution
  Eq. 7: update with CE(labels) + gamma * CE(distill target)

Under strong non-IID this collapses to near-one-hot knowledge (paper Fig. 2),
which is exactly the failure mode DS-FL fixes — so FD must be implemented
faithfully to reproduce the gap."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .client import LocalSpec, local_update, predict_probs
from .losses import HIGHEST

F32 = jnp.float32


def per_label_logits(apply_fn, params, state, x, y, n_classes: int):
    """Eq. 4 for one client -> (t (C, C), present (C,))."""
    probs = predict_probs(apply_fn, params, state, x)       # (I, C)
    oh = jax.nn.one_hot(y, n_classes, dtype=F32)            # (I, C)
    counts = jnp.sum(oh, axis=0)                            # (C,)
    sums = jnp.dot(oh.T, probs, precision=HIGHEST)          # (C, C)
    t = sums / jnp.maximum(counts[:, None], 1.0)
    return t, counts > 0


def aggregate_fd(tk: jax.Array, present: jax.Array):
    """Eq. 5: class-wise mean over owning clients.
    tk: (K, C, C), present: (K, C) -> (t_g (C, C), n_owners (C,)).

    Both cross-client sums are einsum contractions rather than plain
    reduces so their lane order is context-stable: the participation-sparse
    FD round and the dense masked round are different XLA programs summing
    bitwise-identical inputs, and a fused plain reduce is free to
    reassociate differently in each (see `losses.pinned_sum`)."""
    m = present.astype(F32)                                 # (K, C)
    n_own = jnp.einsum("k,kc->c", jnp.ones((m.shape[0],), F32), m,
                       precision=HIGHEST)
    tg = jnp.einsum("kc,kcd->cd", m, tk.astype(F32), precision=HIGHEST) \
        / jnp.maximum(n_own[:, None], 1.0)
    return tg, n_own


def distill_targets(tg, tk_self, n_own, y):
    """Eq. 6 per sample: remove the client's own logit from the average.
    tg: (C, C); tk_self: (C, C); n_own: (C,); y: (I,) -> (I, C)."""
    K_nl = jnp.maximum(n_own, 2.0)                          # guard |K|-1 >= 1
    debias = (K_nl[:, None] * tg - tk_self) / (K_nl[:, None] - 1.0)
    # clients that are sole owner of a class fall back to the global average
    debias = jnp.where((n_own > 1)[:, None], debias, tg)
    return jnp.take(debias, y, axis=0)


def make_fd_round(spec: LocalSpec, n_classes: int, gamma: float = 1.0):
    """One FD round over stacked clients.  Returns updated stacks + the global
    per-class logit (for Fig. 2-style analysis).

    .. deprecated:: prefer ``algorithms.FDAlgorithm`` under
       ``engine.FedEngine`` (same math, unified API)."""

    def round_fn(wk, sk, ok, x, y, rng):
        K = x.shape[0]
        tk, present = jax.vmap(
            lambda w, s, xk, yk: per_label_logits(spec.apply_fn, w, s, xk, yk,
                                                  n_classes))(wk, sk, x, y)
        tg, n_own = aggregate_fd(tk, present)
        rngs = jax.random.split(rng, K)

        def per_client(w, s, o, xk, yk, tkk, rk):
            tgt = distill_targets(tg, tkk, n_own, yk)
            return local_update(spec, w, s, o, xk, yk, rk,
                                distill_extra=tgt, gamma=gamma)

        wk, sk, ok, losses = jax.vmap(per_client)(wk, sk, ok, x, y, tk, rngs)
        return wk, sk, ok, jnp.mean(losses), tg

    return round_fn
