"""Logit aggregation operators (paper Section 3).

Clients upload per-sample probability vectors over the open-batch.  The server
aggregates them into the global logit:

  * SA  (Eq. 16): simple average.
  * ERA (Eq. 13): softmax(average / T) with T << 1 (paper: T = 0.1) —
    intentionally reduces entropy of the ambiguous non-IID average.
  * weighted ERA: reliability-weighted average (paper §5 "future work",
    implemented here as an extension).
  * top-k sparsified exchange: beyond-paper communication optimization for
    large-vocab models; ERA is applied after densifying the mean.

The fused mean+sharpen Pallas kernel lives in ``repro.kernels.era_sharpen``;
``era(..., use_kernel=True)`` routes through it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .losses import HIGHEST, entropy, pinned_sum

F32 = jnp.float32


def sa(local_probs: jax.Array) -> jax.Array:
    """local_probs: (K, ..., C) -> (..., C).  Simple aggregation (Eq. 16)."""
    return jnp.mean(local_probs.astype(F32), axis=0)


def era(local_probs: jax.Array, temperature: float = 0.1,
        use_kernel: bool = False,
        interpret: bool | None = None) -> jax.Array:
    """Entropy-reduction aggregation (Eq. 13): sharpen the mean.

    ``use_kernel=True`` routes through the fused Pallas mean+softmax kernel;
    ``interpret=None`` auto-selects interpret mode on CPU only, so the kernel
    path actually compiles on TPU/GPU instead of silently interpreting."""
    if use_kernel:
        from repro.kernels import ops as kops
        return kops.era_sharpen(local_probs, temperature, interpret=interpret)
    mean = sa(local_probs)
    return jax.nn.softmax(mean / temperature, axis=-1)


def _normalize_weights(weights: jax.Array) -> jax.Array:
    """(K,) nonneg -> normalized; an all-zero vector falls back to uniform
    explicitly instead of silently producing a zero mean.  The total is a
    dot-lowered sum (`losses.pinned_sum`) so the normalization is bitwise
    identical between the dense masked and participation-sparse round
    programs (a plain fused reduce may reassociate per-program)."""
    w = weights.astype(F32)
    total = pinned_sum(w)
    uniform = jnp.full_like(w, 1.0 / w.shape[0])
    return jnp.where(total > 0, w / jnp.maximum(total, 1e-9), uniform)


def _kernel_eligible(local_probs: jax.Array) -> bool:
    """The fused weighted kernel handles the (K, N, C) classification shape;
    higher-rank stacks (the LLM's (K, n, S, V)) keep the einsum path."""
    return local_probs.ndim == 3


def weighted_sa(local_probs: jax.Array, weights: jax.Array,
                use_kernel: bool = False,
                interpret: bool | None = None) -> jax.Array:
    """Weighted simple aggregation: the SA mean restricted to (or biased
    toward) the clients with nonzero weight.  Absent clients (weight 0)
    contribute exactly nothing — `sum(0 * p) == sum()` bitwise for the
    finite probability tensors crossing the wire.  The participation-sparse
    round plane (`algorithms.active_indices`/`scatter_zeros`) rides on this
    guarantee: it never computes absent clients' uploads at all and hands
    this function exact zeros in their lanes instead, which multiply to the
    same exact 0.0 the dense masked stack's lanes do.  ``use_kernel=True``
    routes (K, N, C) stacks through the fused Pallas weighted-mean kernel
    (one VMEM pass, no HBM round-trip for the intermediate)."""
    w = _normalize_weights(weights)
    if use_kernel and _kernel_eligible(local_probs):
        from repro.kernels import ops as kops
        return kops.weighted_mean(local_probs, w, interpret=interpret)
    return jnp.einsum("k,k...->...", w, local_probs.astype(F32),
                      precision=HIGHEST)


def weighted_era(local_probs: jax.Array, weights: jax.Array,
                 temperature: float = 0.1, use_kernel: bool = False,
                 interpret: bool | None = None) -> jax.Array:
    """Reliability-weighted ERA. weights: (K,) nonneg, normalized here.
    An all-zero weight vector falls back to uniform weights explicitly
    (== plain ERA) instead of silently sharpening a zero mean.
    ``use_kernel=True`` fuses weighted mean + sharpen into one VMEM pass
    (`kernels.era_sharpen.weighted_era_sharpen_pallas`) instead of the
    two-pass einsum + softmax."""
    if use_kernel and _kernel_eligible(local_probs):
        from repro.kernels import ops as kops
        return kops.weighted_era_sharpen(
            local_probs, _normalize_weights(weights), temperature,
            interpret=interpret)
    mean = weighted_sa(local_probs, weights)
    return jax.nn.softmax(mean / temperature, axis=-1)


def participation_weights(mask: jax.Array, staleness: jax.Array | None = None,
                          decay: float = 1.0,
                          base: jax.Array | None = None) -> jax.Array:
    """Per-client aggregation weights for a partial-participation round.

    mask: (K,) 0/1 participation (absent clients get exactly zero weight);
    staleness: (K,) rounds since each participant last synced its global
    labels — decayed as ``decay**staleness`` (FedAsync-style staleness
    discount); base: (K,) reliability/base weights to modulate.  Fully
    vectorized (no per-client Python loop), jit/mesh-compatible; feed the
    result to ``weighted_era``/``weighted_sa``/``weighted_average``.

    If every participant decays/modulates to exactly zero (e.g.
    ``decay=0`` with an all-stale cohort), the result falls back to the
    raw mask — uniform over participants, still zero for absent clients —
    so a downstream normalizing average never divides by a zero total."""
    w = mask.astype(F32)
    if base is not None:
        w = w * base.astype(F32)
    if staleness is not None:
        w = w * jnp.power(jnp.asarray(decay, F32), staleness.astype(F32))
    return jnp.where(jnp.sum(w) > 0, w, mask.astype(F32))


def entropy_reliability(local_probs: jax.Array) -> jax.Array:
    """(K, N, C) uploads -> (K,) reliability weights for weighted ERA
    (paper §5 "future work"): the inverse mean entropy of each client's
    soft labels, re-estimated every round, so diffuse (unreliable) uploads
    count less."""
    return 1.0 / (jnp.mean(entropy(local_probs), axis=-1) + 1e-3)


def aggregate(local_probs: jax.Array, method: str = "era",
              temperature: float = 0.1, weights=None,
              use_kernel: bool = False,
              interpret: bool | None = None) -> jax.Array:
    """Dispatch on the paper's aggregation methods.  Whenever ``weights`` is
    given, ``use_kernel=True`` routes through the fused *weighted* Pallas
    kernel (weighted mean + optional sharpen in one VMEM pass) — the
    partial-participation/sim path no longer falls back to einsum+softmax."""
    if method == "sa":
        if weights is not None:
            return weighted_sa(local_probs, weights, use_kernel, interpret)
        return sa(local_probs)
    if method == "era":
        if weights is not None:
            return weighted_era(local_probs, weights, temperature,
                                use_kernel, interpret)
        return era(local_probs, temperature, use_kernel, interpret)
    if method == "weighted_era":
        assert weights is not None
        return weighted_era(local_probs, weights, temperature,
                            use_kernel, interpret)
    raise ValueError(method)


# -------------------------- top-k sparsified exchange (beyond paper) ---------
def topk_compress(probs: jax.Array, k: int):
    """probs: (..., C) -> (values (..., k), indices (..., k)) renormalized.
    The upload payload is k*(4+4) bytes/sample instead of C*4."""
    v, i = jax.lax.top_k(probs, k)
    v = v / jnp.maximum(jnp.sum(v, axis=-1, keepdims=True), 1e-9)
    return v.astype(F32), i.astype(jnp.int32)


def topk_decompress(values: jax.Array, indices: jax.Array, C: int) -> jax.Array:
    """Densify a sparsified distribution back to (..., C)."""
    out = jnp.zeros(values.shape[:-1] + (C,), F32)
    return jnp.put_along_axis(out, indices.astype(jnp.int32),
                              values.astype(F32), axis=-1, inplace=False)


def era_topk(local_values: jax.Array, local_indices: jax.Array, C: int,
             temperature: float = 0.1, k_out: int | None = None):
    """Aggregate sparsified client uploads: fused scatter-accumulate mean ->
    sharpen.  Optionally re-sparsify the global logit for the broadcast leg.

    The K client uploads — ``local_values``/``local_indices`` of shape
    (K, ..., k) over a C-way class axis — are scatter-added straight into
    one (..., C) accumulator, so the mean costs O(N·C + K·N·k) memory
    instead of materializing all K densified (..., C) copies (the old
    ``vmap(topk_decompress)`` path was O(K·N·C) — prohibitive for
    large-vocab LLM exchanges).  Equivalence with the dense path is pinned
    in tests/test_aggregation.py."""
    K = local_values.shape[0]
    kk = local_values.shape[-1]
    inner = local_values.shape[1:-1]               # row dims, e.g. (N,) / (n, S)
    n = 1
    for d in inner:
        n *= d
    # fold the client axis into the per-row slot axis: each of the n rows
    # scatter-accumulates its K*k (index, value) pairs in one segment-sum
    val = jnp.moveaxis(local_values.astype(F32), 0, -2).reshape(n, K * kk)
    idx = jnp.moveaxis(local_indices.astype(jnp.int32), 0, -2).reshape(n, K * kk)
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    mean = (jnp.zeros((n, C), F32).at[rows, idx].add(val) / K).reshape(
        inner + (C,))
    g = jax.nn.softmax(mean / temperature, axis=-1)
    if k_out is not None:
        return topk_compress(g, k_out)
    return g
