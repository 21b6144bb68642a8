"""Jit-ready wrappers around the Pallas kernels (with custom VJPs where the
training path needs gradients).  ``INTERPRET = None`` means auto: interpret
mode on CPU (this container), the compiled kernel on TPU/GPU.  Set it to
True/False to force either mode globally, or pass ``interpret=`` per call
where the wrapper exposes it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .distill_loss import distill_loss_bwd_pallas, distill_loss_fwd_pallas
from .era_sharpen import (era_sharpen_pallas, resolve_interpret,
                          weighted_era_sharpen_pallas)
from .ssd_chunk import ssd_chunk_pallas

INTERPRET: bool | None = None     # None = auto (CPU -> interpret, else compiled)
F32 = jnp.float32


def _interp(flag: bool | None = None) -> bool:
    return resolve_interpret(INTERPRET if flag is None else flag)


# ------------------------------------------------------------ era_sharpen ----
def era_sharpen(local_probs: jax.Array, temperature: float = 0.1,
                interpret: bool | None = None) -> jax.Array:
    """(K, N, C) -> (N, C).  Teacher construction — not differentiated.
    Any N (the kernel pads the row axis to its block internally)."""
    return era_sharpen_pallas(jax.lax.stop_gradient(local_probs), temperature,
                              interpret=_interp(interpret))


def weighted_era_sharpen(local_probs: jax.Array, weights: jax.Array,
                         temperature: float = 0.1,
                         interpret: bool | None = None) -> jax.Array:
    """(K, N, C) x (K,) normalized weights -> (N, C): weighted mean + sharpen
    fused in one VMEM pass (the partial-participation teacher).  Zero-weight
    clients contribute exactly nothing.  Not differentiated."""
    return weighted_era_sharpen_pallas(
        jax.lax.stop_gradient(local_probs), jax.lax.stop_gradient(weights),
        temperature, interpret=_interp(interpret))


def weighted_mean(local_probs: jax.Array, weights: jax.Array,
                  interpret: bool | None = None) -> jax.Array:
    """(K, N, C) x (K,) normalized weights -> (N, C) weighted mean (the
    fused ``weighted_sa`` route: same kernel, softmax skipped)."""
    return weighted_era_sharpen_pallas(
        jax.lax.stop_gradient(local_probs), jax.lax.stop_gradient(weights),
        sharpen=False, interpret=_interp(interpret))


# ------------------------------------------------------------ distill loss ---
@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def distill_loss_2d(z: jax.Array, t: jax.Array,
                    interpret: bool | None = None) -> jax.Array:
    losses, _ = distill_loss_fwd_pallas(z, t, interpret=_interp(interpret))
    return jnp.mean(losses)


def _dl_fwd(z, t, interpret):
    losses, logz = distill_loss_fwd_pallas(z, t, interpret=_interp(interpret))
    tmass = jnp.sum(t.astype(F32), axis=-1)
    return jnp.mean(losses), (z, t, logz, tmass)


def _dl_bwd(interpret, res, g):
    z, t, logz, tmass = res
    n = z.shape[0]
    gscale = jnp.reshape(g.astype(F32) / n, (1,))
    dz = distill_loss_bwd_pallas(z, t, logz, tmass, gscale,
                                 interpret=_interp(interpret))
    return dz, None


distill_loss_2d.defvjp(_dl_fwd, _dl_bwd)


def distill_loss(student_logits: jax.Array, teacher_probs: jax.Array,
                 mask=None, interpret: bool | None = None) -> jax.Array:
    """Arbitrary leading dims.  The kernel has no row mask: a masked loss
    raises rather than quietly running the reference (masked callers use
    `core.losses.softmax_xent`).  ``interpret=None`` = auto (CPU ->
    interpret, else the compiled kernel)."""
    if mask is not None:
        raise NotImplementedError(
            "the fused distill_loss kernel takes no mask; use "
            "core.losses.softmax_xent for a masked distillation loss")
    V = student_logits.shape[-1]
    z = student_logits.reshape(-1, V)
    t = teacher_probs.reshape(-1, V)
    return distill_loss_2d(z, t, interpret)


# -------------------------------------------------------------- ssd chunk ----
def ssd_chunk(xr, dtr, dAr, Br, Cr, hpg: int) -> jax.Array:
    """Drop-in replacement for models.ssm._chunk_local:
    xr: (B, nc, Q, H, P) etc. -> (B, nc, Q, H, P) fp32."""
    B, nc, Q, H, P = xr.shape
    G, N = Br.shape[3], Br.shape[4]
    x2 = xr.reshape(B * nc, Q, H, P)
    dt2 = dtr.reshape(B * nc, Q, H)
    dA2 = dAr.reshape(B * nc, Q, H)
    B2 = Br.reshape(B * nc, Q, G, N)
    C2 = Cr.reshape(B * nc, Q, G, N)
    y = ssd_chunk_pallas(x2, dt2, dA2, B2, C2, interpret=_interp())
    return y.reshape(B, nc, Q, H, P)
