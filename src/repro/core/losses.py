"""Loss functions (fp32 statistics).  The distillation loss has a fused
Pallas path (`repro.kernels.distill_loss`) selected by ``use_kernel``.

The scalar loss means are computed through ``pinned_mean``: XLA
reassociates a plain fused ``reduce`` differently depending on the
surrounding program, so the *same* per-sample CE values can mean to
different last-bit floats in two differently-shaped programs — which would
break the participation-sparse round's bitwise-parity guarantee (the
sparse and the dense masked rounds are different programs computing
identical per-client losses).  A ``dot``-lowered sum is emitted through
XLA's dot path, whose lane order is context-stable (empirically: every
einsum/matmul in the round is, only plain reduces wobble), and it batches
cleanly under ``vmap``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
# for f32 contractions that stand for exact sums: the TPU would otherwise
# run them as one bf16 pass (~3 significant digits); the CPU ignores it
HIGHEST = jax.lax.Precision.HIGHEST


def pinned_sum(v):
    """Context-stable summation: lowered as a dot, not a plain reduce, so
    two differently-fused programs summing bitwise-identical inputs agree
    bitwise (see module docstring).  Sums over *all* axes."""
    v = v.astype(F32).ravel()
    return jnp.dot(v, jnp.ones_like(v), precision=HIGHEST)


def pinned_mean(ce, mask=None):
    """Mean (or mask-weighted mean) of a per-sample loss tensor, with the
    reduction order pinned across programs (see module docstring)."""
    if mask is not None:
        return pinned_sum(ce * mask) / jnp.maximum(pinned_sum(mask), 1.0)
    return pinned_sum(ce) / ce.size


def log_softmax(logits):
    x = logits.astype(F32)
    m = jnp.max(x, axis=-1, keepdims=True)
    s = x - m
    return s - jnp.log(jnp.sum(jnp.exp(s), axis=-1, keepdims=True))


def softmax_xent(logits, labels_onehot, mask=None):
    """Cross-entropy vs hard one-hot or soft targets. logits: (..., C)."""
    ls = log_softmax(logits)
    ce = -jnp.sum(labels_onehot.astype(F32) * ls, axis=-1)
    return pinned_mean(ce, mask)


def xent_int_labels(logits, labels, mask=None):
    """CE with integer labels, avoids materializing one-hots over big vocabs."""
    ls = log_softmax(logits)
    ce = -jnp.take_along_axis(ls, labels[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]
    return pinned_mean(ce, mask)


def distill_xent(student_logits, teacher_probs, mask=None, use_kernel=False,
                 interpret=None):
    """KD loss: CE(teacher_probs || softmax(student_logits)).  This is the
    DS-FL "6. Distillation" objective (Eq. 10) with the global logit as soft
    target.  On the kernel path ``interpret=None`` auto-selects interpret
    mode on CPU only (the `kernels.ops` convention), so the fused kernel
    actually compiles on TPU/GPU; pass True/False to force either mode."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.distill_loss(student_logits, teacher_probs, mask,
                                 interpret=interpret)
    return softmax_xent(student_logits, teacher_probs, mask)


def topk_distill_xent(student_logits, topk_p, topk_i, mask=None):
    """KD against a sparsified teacher: sum over the k kept entries only.
    topk_p: (..., k) renormalized probs; topk_i: (..., k) vocab indices."""
    ls = log_softmax(student_logits)
    sel = jnp.take_along_axis(ls, topk_i.astype(jnp.int32), axis=-1)
    ce = -jnp.sum(topk_p.astype(F32) * sel, axis=-1)
    return pinned_mean(ce, mask)


def entropy(probs, axis=-1):
    p = probs.astype(F32)
    return -jnp.sum(p * jnp.log(jnp.clip(p, 1e-12, 1.0)), axis=axis)


def accuracy(logits, labels):
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(F32))
