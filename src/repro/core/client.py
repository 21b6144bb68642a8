"""Client-local training loops (jit/vmap-able building blocks).

A "client model" is any functional pair ``apply(params, state, x, train)``
-> ``(logits, new_state)`` (the smallnets API; LLM wrappers adapt to it).
All loops are pure ``lax.scan`` so a whole federated round jits as one XLA
program, and `over_clients` lifts them over the client axis.
"""
from __future__ import annotations

import math
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


# One client's step maps (runs clients one after another) when its largest
# contraction has at least this many multiply-adds.  TPU v5e, DS-FL round,
# lax.map against vmap at K=4..100 (benchmarks/client_loop_bench.py):
# steps up to 9.2e7 (tiny_mlp, the IMDb LSTM, 16-pixel CNNs) run as fast or
# faster under vmap, by up to 1.6 times; steps from 5.1e8 up (the paper's
# two CNNs and its Reuters DNN) run 1.07-3.6 times faster under map.
MAP_MIN_MACS = 1 << 27


def _largest_contraction(jaxpr) -> int:
    """Multiply-adds of the largest ``dot_general`` or
    ``conv_general_dilated`` in ``jaxpr`` or in a jaxpr in its equations'
    parameters (scan and while bodies, cond branches, pjit and
    custom-derivative calls)."""
    most = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            (lhs_c, _), _ = eqn.params["dimension_numbers"]
            lhs = eqn.invars[0].aval.shape
            per_out = math.prod(lhs[d] for d in lhs_c)
        elif eqn.primitive.name == "conv_general_dilated":
            rhs = eqn.invars[1].aval     # its out-feature dim, then the rest
            per_out = rhs.size // rhs.shape[
                eqn.params["dimension_numbers"].rhs_spec[0]]
        else:
            per_out = 0
        if per_out:
            most = max(most, eqn.outvars[0].aval.size * per_out)
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                sub = getattr(sub, "jaxpr", sub)      # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    most = max(most, _largest_contraction(sub))
    return most


def loop_path(fn: Callable, *args) -> str:
    """``"map"`` if one client's ``fn`` (its arrays: ``args`` without their
    leading client axis) traces to a contraction of at least
    `MAP_MIN_MACS` multiply-adds, else ``"vmap"``: the lowering
    `over_clients` gives ``fn``."""
    one = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                       args)
    big = _largest_contraction(jax.make_jaxpr(fn)(*one).jaxpr)
    return "map" if big >= MAP_MIN_MACS else "vmap"


def over_clients(fn: Callable, *args):
    """``fn`` (one client's arrays -> one client's results) over the leading
    client axis of ``args``, results stacked along it.

    ``jax.vmap`` batches every client's step into one, which keeps a small
    step's ops busy but multiplies a large step's working set by K and
    folds the client axis of a convolution into ``feature_group_count``
    (one group per client), which the MXU runs far below a plain
    convolution.  So a step large enough to fill the chip alone runs one
    client at a time under ``lax.map`` (each client's whole epoch scan
    inside, every convolution ungrouped), and a smaller one under
    ``jax.vmap`` (`loop_path`).  Either way each client sees the same
    inputs and keys."""
    if loop_path(fn, *args) == "map":
        return jax.lax.map(lambda a: fn(*a), args)
    return jax.vmap(fn)(*args)


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
