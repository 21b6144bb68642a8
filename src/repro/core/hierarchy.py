"""Two-level (edge → server) ERA aggregation (`core.hierarchy`).

At fleet scale the K client uploads do not land on one box: edge
aggregators each reduce a contiguous shard of the cohort's (K, n, C)
probability stack to a single weighted partial sum, and the server adds
the ``n_edges`` partials and sharpens the result — the wire between edges
and server carries ``n_edges`` (n, C) tensors instead of K of them.

Parity contract (pinned by ``tests/test_cohort.py``):

* Weights are normalized **globally first** (`aggregation._normalize_weights`,
  whose total is the dot-lowered `losses.pinned_sum` — see that module's
  associativity note), so every edge scales its lanes by exactly the
  coefficients the flat einsum would use.  With ``n_edges=1`` the single
  "edge" computes the identical ``einsum("k,k...->...")`` over the identical
  operands, and the result is **bitwise** equal to `aggregation.weighted_sa`
  / `weighted_era` — the flat path is literally a special case.
* With ``n_edges >= 2`` the cross-client reduction is re-associated: the
  flat einsum accumulates all K lanes in one contraction, while the tree
  sums per-shard partials.  Floating-point addition is not associative, so
  bitwise parity is *not* promised — the contract degrades to a pinned
  tolerance (~1e-6 relative for f32 probability stacks; each extra tree
  level can add one more rounding of order eps * ||mean||).  What **is**
  exact at any depth: zero-weight lanes still contribute exactly nothing
  (0.0 * x == 0.0 inside whichever shard they fall), so the participation
  masking / sparse-plane guarantees survive hierarchy unchanged.

``use_kernel=True`` routes each edge's partial through the fused Pallas
weighted-mean kernel (`kernels.ops.weighted_mean`) for (K, N, C) stacks —
the per-shard reduce is exactly the flat kernel's job on a smaller K.  The
server stage (add ``n_edges`` partials, sharpen) is O(n_edges * n * C) and
stays in plain jnp.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .aggregation import _kernel_eligible, _normalize_weights
from .losses import HIGHEST

F32 = jnp.float32


def edge_shards(K: int, n_edges: int) -> list[tuple[int, int]]:
    """Contiguous ``[start, end)`` client shards, one per edge aggregator.
    Sizes differ by at most one; every client belongs to exactly one edge."""
    if not 1 <= n_edges <= K:
        raise ValueError(f"n_edges {n_edges} not in [1, {K}]")
    base, extra = divmod(K, n_edges)
    bounds, start = [], 0
    for e in range(n_edges):
        end = start + base + (1 if e < extra else 0)
        bounds.append((start, end))
        start = end
    return bounds


def hierarchical_weighted_sa(local_probs: jax.Array, weights: jax.Array,
                             n_edges: int = 1, use_kernel: bool = False,
                             interpret: bool | None = None) -> jax.Array:
    """Edge-sharded weighted mean: globally-normalized weights, per-edge
    partial sums, server adds the partials in edge order.  ``n_edges=1`` is
    bitwise `aggregation.weighted_sa`; deeper trees carry the tolerance
    contract documented in the module docstring."""
    w = _normalize_weights(weights)
    probs = local_probs.astype(F32)
    if n_edges == 1:
        # the flat path, verbatim (kernel route included) — bitwise anchor
        if use_kernel and _kernel_eligible(probs):
            from repro.kernels import ops as kops
            return kops.weighted_mean(probs, w, interpret=interpret)
        return jnp.einsum("k,k...->...", w, probs, precision=HIGHEST)
    partials = []
    for start, end in edge_shards(probs.shape[0], n_edges):
        if use_kernel and _kernel_eligible(probs):
            from repro.kernels import ops as kops
            partials.append(kops.weighted_mean(probs[start:end],
                                               w[start:end],
                                               interpret=interpret))
        else:
            partials.append(jnp.einsum("k,k...->...", w[start:end],
                                       probs[start:end],
                                       precision=HIGHEST))
    # server stage: fixed left-to-right edge order, so the tree's rounding
    # is at least deterministic across runs of the same topology
    total = partials[0]
    for p in partials[1:]:
        total = total + p
    return total


def hierarchical_weighted_era(local_probs: jax.Array, weights: jax.Array,
                              temperature: float = 0.1, n_edges: int = 1,
                              use_kernel: bool = False,
                              interpret: bool | None = None) -> jax.Array:
    """Two-level ERA (Eq. 13 over an edge tree): edges reduce their shards,
    the server adds the partials and sharpens.  Note the kernel route here
    fuses *per edge* (weighted mean in VMEM) and sharpens at the server —
    unlike flat `weighted_era`'s single fused mean+sharpen kernel, the
    sharpen cannot live on an edge, since softmax of a partial sum is not a
    partial softmax."""
    mean = hierarchical_weighted_sa(local_probs, weights, n_edges,
                                    use_kernel, interpret)
    return jax.nn.softmax(mean / temperature, axis=-1)
