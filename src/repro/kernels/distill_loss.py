"""Vocab-blocked fused KD cross-entropy (the DS-FL "6. Distillation" loss).

CE(t || softmax(z)) per row, streaming over vocabulary tiles with an online
logsumexp — the full softmax is never materialized in HBM, which is the
memory hot-spot of distillation at LLM vocab sizes (bs x seq x 152k).

Grid: (N / bn, V / bv) with the vocab axis innermost; fp32 running
(max, sumexp, teacher-dot, teacher-mass) live in VMEM scratch across vocab
steps.  The backward kernel recomputes softmax from the saved per-row logZ.

TPU layout: every per-row quantity (the scratch accumulators, the loss and
logZ outputs, the backward's logZ and teacher-mass inputs) is a 2-D
``(bn, 128)`` tile replicated across the 128 lanes, so each block's last two
dims are (8, 128)-aligned.  A vocabulary the block does not divide (qwen's
151,936 = 128 x 1187) is padded up to the block with very negative logits
and zero teacher mass: padded columns add ``exp(pad - m) == 0`` to the
partition sum and ``0 * pad == 0`` to the teacher dot, so per-row results
are unchanged.  The pad is ``NEG`` or the dtype's most negative finite value,
whichever is larger: ``NEG`` itself would round to -inf in float16, and
``0 * -inf`` is NaN.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .era_sharpen import resolve_interpret

F32 = jnp.float32
NEG = -1e30
LANES = 128


def _fwd_kernel(z_ref, t_ref, loss_ref, lz_ref, m_s, l_s, td_s, tm_s, *,
                nv: int):
    v = pl.program_id(1)

    @pl.when(v == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG)
        l_s[...] = jnp.zeros_like(l_s)
        td_s[...] = jnp.zeros_like(td_s)
        tm_s[...] = jnp.zeros_like(tm_s)

    z = z_ref[...].astype(F32)                                # (bn, bv)
    t = t_ref[...].astype(F32)
    m_old = m_s[...]                                          # (bn, 128)
    m_new = jnp.maximum(m_old, jnp.max(z, axis=-1, keepdims=True))
    corr = jnp.exp(m_old - m_new)
    l_s[...] = l_s[...] * corr + jnp.sum(jnp.exp(z - m_new[:, :1]), axis=-1,
                                         keepdims=True)
    m_s[...] = m_new
    td_s[...] = td_s[...] + jnp.sum(t * z, axis=-1, keepdims=True)
    tm_s[...] = tm_s[...] + jnp.sum(t, axis=-1, keepdims=True)

    @pl.when(v == nv - 1)
    def _finish():
        logz = m_s[...] + jnp.log(l_s[...])
        loss_ref[...] = tm_s[...] * logz - td_s[...]
        lz_ref[...] = logz


def _bwd_kernel(gscale_ref, z_ref, t_ref, lz_ref, tm_ref, dz_ref):
    z = z_ref[...].astype(F32)
    t = t_ref[...].astype(F32)
    p = jnp.exp(z - lz_ref[...][:, :1])
    g = gscale_ref[0, 0]
    dz_ref[...] = (g * (p * tm_ref[...][:, :1] - t)).astype(dz_ref.dtype)


def _blocks(N: int, V: int, block_n: int, block_v: int):
    """Block sizes and the padded (N, V) extent they tile."""
    bn, bv = min(block_n, N), min(block_v, V)
    return bn, bv, N + (-N) % bn, V + (-V) % bv


def _pad(a, n_pad: int, v_pad: int, fill):
    N, V = a.shape
    if (n_pad, v_pad) == (N, V):
        return a
    return jnp.pad(a, ((0, n_pad - N), (0, v_pad - V)), constant_values=fill)


def _neg(dtype) -> float:
    """The logit pad: ``NEG``, or ``dtype``'s most negative finite value."""
    return max(NEG, float(jnp.finfo(dtype).min))


def _rows(a):
    """(N,) per-row values -> the kernel's lane-replicated (N, 128) tile."""
    return jnp.broadcast_to(a.astype(F32)[:, None], (a.shape[0], LANES))


def distill_loss_fwd_pallas(z: jax.Array, t: jax.Array, block_n: int = 256,
                            block_v: int = 2048,
                            interpret: bool | None = None):
    """z, t: (N, V) -> (per-row loss (N,), logZ (N,)).  Any N and V (both
    padded up to the block).  ``interpret=None`` = auto (the `kernels.ops`
    convention: interpret on CPU, compiled elsewhere)."""
    interpret = resolve_interpret(interpret)
    N, V = z.shape
    bn, bv, n_pad, v_pad = _blocks(N, V, block_n, block_v)
    z = _pad(z, n_pad, v_pad, _neg(z.dtype))
    t = _pad(t, n_pad, v_pad, 0)
    row = pl.BlockSpec((bn, LANES), lambda n, v: (n, 0))
    loss, logz = pl.pallas_call(
        functools.partial(_fwd_kernel, nv=v_pad // bv),
        grid=(n_pad // bn, v_pad // bv),
        in_specs=[pl.BlockSpec((bn, bv), lambda n, v: (n, v)),
                  pl.BlockSpec((bn, bv), lambda n, v: (n, v))],
        out_specs=[row, row],
        out_shape=[jax.ShapeDtypeStruct((n_pad, LANES), F32),
                   jax.ShapeDtypeStruct((n_pad, LANES), F32)],
        scratch_shapes=[pltpu.VMEM((bn, LANES), F32) for _ in range(4)],
        interpret=interpret,
    )(z, t)
    return loss[:N, 0], logz[:N, 0]


def distill_loss_bwd_pallas(z, t, logz, tmass, gscale, block_n: int = 256,
                            block_v: int = 2048,
                            interpret: bool | None = None):
    """Gradient wrt z: gscale * (softmax(z) * tmass - t).  logz/tmass: (N,);
    gscale: (1,) f32.  ``interpret=None`` = auto (CPU -> interpret, else
    compiled)."""
    interpret = resolve_interpret(interpret)
    N, V = z.shape
    bn, bv, n_pad, v_pad = _blocks(N, V, block_n, block_v)
    row = pl.BlockSpec((bn, LANES), lambda n, v: (n, 0))
    tile = pl.BlockSpec((bn, bv), lambda n, v: (n, v))
    dz = pl.pallas_call(
        _bwd_kernel,
        grid=(n_pad // bn, v_pad // bv),
        in_specs=[pl.BlockSpec((1, 1), lambda n, v: (0, 0),
                               memory_space=pltpu.SMEM),
                  tile, tile, row, row],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n_pad, v_pad), z.dtype),
        interpret=interpret,
    )(jnp.reshape(gscale.astype(F32), (1, 1)),
      _pad(z, n_pad, v_pad, _neg(z.dtype)), _pad(t, n_pad, v_pad, 0),
      _pad(_rows(logz), n_pad, LANES, 0), _pad(_rows(tmass), n_pad, LANES, 0))
    return dz[:N, :V]
