"""Pallas kernel for the within-chunk ('diagonal') SSD block of Mamba2.

Per (chunk, head) tile: scores = (C B^T) * exp(segsum(dA)) * dt, y = scores x.
Tile shapes are MXU-aligned for the production configs (Q=256, N=128, P=64):
the (Q, N) x (N, Q) and (Q, Q) x (Q, P) matmuls hit the systolic array and
the whole working set (~Q*(2N+P+Q) fp32 ~ 0.6 MB) sits in VMEM.

Grid: (M, H) with M = batch * n_chunks.  The kernel works head-major — x/y
as (M, H, Q, P), B/C as (M, G, Q, N) — so every tile's last two dims are a
whole (Q, P) or (Q, N) matrix; B/C blocks are indexed by the head's group
(GQA-style head->group map done in the BlockSpec index_map).  The per-step
scalars dt and cumsum(dA) are laid out (M, H, 1, Q): a (1, 1, 1, Q) block's
last two dims equal the array's, so each grid step fetches its head's row.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .era_sharpen import resolve_interpret

F32 = jnp.float32


def _kernel(x_ref, dt_ref, cum_ref, b_ref, c_ref, y_ref):
    # x/y: (1, 1, Q, P); dt/cum: (1, 1, 1, Q); b/c: (1, 1, Q, N)
    x = x_ref[0, 0].astype(F32)                               # (Q, P)
    B = b_ref[0, 0].astype(F32)                               # (Q, N)
    C = c_ref[0, 0].astype(F32)
    dt = dt_ref[0, 0].astype(F32)                             # (1, Q)
    cum = cum_ref[0, 0]
    Q = x.shape[0]
    cols = jnp.broadcast_to(cum, (Q, Q))                      # [i, j] = cum_j
    T = cols.T - cols                                         # cum_i - cum_j
    ii = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    L = jnp.where(ii >= jj, jnp.exp(T), 0.0)
    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=F32)  # (Q, Q)
    W = scores * L * dt
    y_ref[0, 0] = jnp.dot(W, x, preferred_element_type=F32)


def ssd_chunk_pallas(x, dt, dA, Bm, Cm, interpret: bool | None = None):
    """x: (M, Q, H, P); dt/dA: (M, Q, H); Bm/Cm: (M, Q, G, N) -> (M, Q, H, P).
    fp32 output (cast by the caller).  ``interpret=None`` = auto (CPU ->
    interpret, else compiled)."""
    interpret = resolve_interpret(interpret)
    M, Q, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    hpg = H // G
    cum = jnp.cumsum(dA.astype(F32), axis=1)
    y = pl.pallas_call(
        _kernel,
        grid=(M, H),
        in_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda m, h: (m, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda m, h: (m, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q), lambda m, h: (m, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda m, h: (m, h // hpg, 0, 0)),
            pl.BlockSpec((1, 1, Q, N), lambda m, h: (m, h // hpg, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, Q, P), lambda m, h: (m, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((M, H, Q, P), F32),
        interpret=interpret,
    )(x.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1)[:, :, None],
      cum.transpose(0, 2, 1)[:, :, None],
      Bm.transpose(0, 2, 1, 3), Cm.transpose(0, 2, 1, 3))
    return y.transpose(0, 2, 1, 3)
