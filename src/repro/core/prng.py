"""O(m) access to rows of ``jax.random.split(key, K)`` without the (K,) split.

The cohort-resident round plane (`core.cohort`, `sim.runner.CohortRunner`)
keeps only the sampled m clients resident, but the house RNG discipline
derives client k's per-round key as row k of ``jax.random.split(r, K)`` —
an O(K) array the million-client path must never materialize.

Under JAX's default threefry PRNG with the partitionable layout (the
default since jax 0.5, ``jax_threefry_partitionable=True``) the split *is*
a counter-mode block cipher in which every row owns its counter: row k of
``split(key, K)`` is the threefry2x32 encryption of the 64-bit counter k,
i.e. of the word pair ``(hi, lo) = (0, k)``, independent of K.
`split_take` batches that: m rows cost one threefry call over 2m counter
words, and the result is **bitwise** the corresponding rows of the dense
split (pinned by ``tests/test_cohort.py`` across odd/even K and
hypothesis-drawn ids).

Anything else (typed keys of another impl, a non-default global impl, or
``jax_threefry_partitionable=False``) falls back to the dense
``jnp.take(jax.random.split(key, K), ids, axis=0)``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _is_raw_threefry(key) -> bool:
    """Raw uint32 (2,) keys are threefry keys by construction (the repo's
    ``jax.random.PRNGKey`` discipline); typed keys carry their impl.  The
    counter shortcut holds only under the partitionable split layout."""
    if not jax.config.jax_threefry_partitionable:
        return False
    if jnp.issubdtype(jnp.result_type(key), jax.dtypes.prng_key):
        return False
    return key.shape == (2,) and key.dtype == jnp.uint32


def split_rows(key, ids, num: int):
    """Rows ``ids`` of ``jax.random.split(key, num)``, computed in O(|ids|).

    ``ids``: (m,) integer client ids in ``[0, num)`` (traced or concrete);
    returns (m, 2) uint32 raw keys, bitwise equal to
    ``jnp.take(jax.random.split(key, num), ids, axis=0)``.
    """
    if not _is_raw_threefry(key):
        # mode="clip": typed key dtypes reject jnp.take's default fill mode
        return jnp.take(jax.random.split(key, num), jnp.asarray(ids), axis=0,
                        mode="clip")
    from jax.extend.random import threefry_2x32
    lo = jnp.asarray(ids).astype(jnp.uint32)
    # the primitive encrypts the halves of its count array pairwise:
    # out[:m] are the rows' first words, out[m:] their second words
    out = threefry_2x32(key, jnp.concatenate([jnp.zeros_like(lo), lo]))
    return jnp.stack([out[: lo.shape[0]], out[lo.shape[0]:]], axis=-1)


# the name the algorithms use: "take rows of split(key, num)"
split_take = split_rows
