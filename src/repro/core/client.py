"""Client-local training loops (jit/vmap-able building blocks).

A "client model" is any functional pair ``apply(params, state, x, train)``
-> ``(logits, new_state)`` (the smallnets API; LLM wrappers adapt to it).
All loops are pure ``lax.scan`` so a whole federated round jits as one XLA
program and ``jax.vmap`` lifts them over the client axis.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable

import jax
import jax.numpy as jnp

from ..optim.optimizers import Optimizer
from .losses import distill_xent, pinned_mean, softmax_xent, xent_int_labels


@dataclass(frozen=True)
class LocalSpec:
    apply_fn: Callable
    opt: Optimizer
    epochs: int
    batch_size: int


def _epoch_perm(key, n_items: int, batch_size: int) -> jax.Array:
    nb = n_items // batch_size
    return jax.random.permutation(key, n_items)[: nb * batch_size
                                                ].reshape(nb, batch_size)


def local_update(spec: LocalSpec, params, state, opt_state, x, y, rng,
                 distill_extra=None, gamma: float = 0.0):
    """E epochs of minibatch supervised training on one client's private data.
    ``distill_extra`` is an optional per-sample soft-target array ``(I, C)``
    aligned with ``x``; when given it adds the FD regularizer (Eq. 7):
    gamma * CE(distill targets) on the *private* inputs."""
    n = x.shape[0]
    # clamp like local_distill: batch_size > n would give zero batches per
    # epoch — an empty scan and jnp.mean over zero losses -> NaN metrics
    bs = min(spec.batch_size, n)

    def batch_step(carry, idx):
        params, st, ostate, step = carry
        xb = jnp.take(x, idx, axis=0)
        yb = jnp.take(y, idx, axis=0)

        def loss_fn(p, s):
            logits, ns = spec.apply_fn(p, s, xb, True)
            loss = xent_int_labels(logits, yb)
            if distill_extra is not None:
                tgt = jnp.take(distill_extra, idx, axis=0)
                loss = loss + gamma * softmax_xent(logits, tgt)
            return loss, ns

        (loss, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(params, st)
        params, ostate = spec.opt.update(g, params, ostate, step)
        return (params, ns, ostate, step + 1), loss

    def epoch_step(carry, ekey):
        perm = _epoch_perm(ekey, n, bs)
        carry, losses = jax.lax.scan(batch_step, carry, perm)
        return carry, pinned_mean(losses)

    carry = (params, state, opt_state, jnp.int32(0))
    carry, losses = jax.lax.scan(epoch_step, carry,
                                 jax.random.split(rng, spec.epochs))
    params, state, opt_state, _ = carry
    return params, state, opt_state, pinned_mean(losses)


def local_distill(spec: LocalSpec, params, state, opt_state, x_open,
                  teacher_probs, rng):
    """DS-FL "6. Distillation" (Eq. 10): train on the open batch against the
    broadcast global logit."""
    n = x_open.shape[0]
    bs = min(spec.batch_size, n)

    def batch_step(carry, idx):
        params, st, ostate, step = carry
        xb = jnp.take(x_open, idx, axis=0)
        tb = jnp.take(teacher_probs, idx, axis=0)

        def loss_fn(p, s):
            logits, ns = spec.apply_fn(p, s, xb, True)
            return distill_xent(logits, tb), ns

        (loss, ns), g = jax.value_and_grad(loss_fn, has_aux=True)(params, st)
        params, ostate = spec.opt.update(g, params, ostate, step)
        return (params, ns, ostate, step + 1), loss

    def epoch_step(carry, ekey):
        perm = _epoch_perm(ekey, n, bs)
        carry, losses = jax.lax.scan(batch_step, carry, perm)
        return carry, pinned_mean(losses)

    carry = (params, state, opt_state, jnp.int32(0))
    carry, losses = jax.lax.scan(epoch_step, carry,
                                 jax.random.split(rng, spec.epochs))
    params, state, opt_state, _ = carry
    return params, state, opt_state, pinned_mean(losses)


def _softmax(logits):
    """Class probabilities of the prediction leg.  The barrier keeps the
    softmax out of the logits' producer fusion: the v5e compiler (libtpu
    in jax 0.9) overflows its stack costing a vmapped MNIST-CNN forward
    with the softmax fused in (K=10 clients on a 1000-image open batch).
    The barrier is an identity, so values are unchanged."""
    logits = jax.lax.optimization_barrier(logits.astype(jnp.float32))
    return jax.nn.softmax(logits, axis=-1)


def predict_probs(apply_fn: Callable, params, state, x, batch_size: int = 0):
    """Inference probabilities on the open batch ("2. Prediction", Eq. 9).

    ``batch_size > 0`` chunks the forward pass with ``lax.map`` so large open
    batches never materialize one giant activation set (the tail chunk is
    wrap-padded and the padding rows dropped)."""
    n = x.shape[0]
    if batch_size <= 0 or batch_size >= n:
        logits, _ = apply_fn(params, state, x, False)
        return _softmax(logits)
    nb = -(-n // batch_size)
    pad = nb * batch_size - n
    if pad:
        x = jnp.concatenate([x, x[:pad]], axis=0)
    chunks = x.reshape((nb, batch_size) + x.shape[1:])

    def chunk_probs(xb):
        logits, _ = apply_fn(params, state, xb, False)
        return _softmax(logits)

    probs = jax.lax.map(chunk_probs, chunks)
    return probs.reshape((nb * batch_size,) + probs.shape[2:])[:n]
