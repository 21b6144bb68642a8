"""`LanePlan`: the one place a round decides which client lanes it computes.

Pins, per plane: the full plane hands every argument back as the same
object (the full round's program gains no op); the masked plane selects;
the sparse plane is bitwise the hand-written `active_indices` /
`gather_clients` / `select_clients` / `scatter_clients` / `scatter_zeros`
sequence.  And the decision: a budget below K goes sparse, a budget of K
or more stays masked, and each algorithm's veto (DS-FL's ``corrupt``, the
LLM round's ``hp.topk``) keeps every lane."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import llm_dsfl
from repro.core.algorithms import (BatchCtx, DSFLAlgorithm, DSFLConfig,
                                   LanePlan, active_indices, client_keys,
                                   gather_clients, masked_mean,
                                   scatter_clients, scatter_zeros,
                                   select_clients)
from repro.models.smallnets import apply_tiny_mlp

K, M = 5, 2
MASK = jnp.array([0.0, 1.0, 0.0, 1.0, 0.0])
WEIGHTS = MASK * 0.7
HP = DSFLConfig(rounds=1, local_epochs=1, distill_epochs=1, batch_size=4,
                open_batch=4)


def _ctx(**kw):
    return BatchCtx(x=jnp.zeros((K, 3)), **kw)


def _dsfl(corrupt=None):
    return DSFLAlgorithm(apply_tiny_mlp, HP, corrupt=corrupt)


SPARSE = dict(mask=MASK, active_budget=M)
CASES = {
    # name: (plan maker, the plane it must pick)
    "full": (lambda: LanePlan.of(K, None, M), "full"),
    "masked": (lambda: LanePlan.for_round(_ctx(mask=MASK)), "masked"),
    "budget_ge_K": (lambda: LanePlan.for_round(_ctx(mask=MASK,
                                                    active_budget=K)),
                    "masked"),
    "sparse": (lambda: LanePlan.for_round(_ctx(**SPARSE)), "sparse"),
    "dsfl_sparse": (lambda: _dsfl()._lanes(_ctx(**SPARSE)), "sparse"),
    "dsfl_corrupt": (lambda: _dsfl(lambda p, x, r: p)._lanes(_ctx(**SPARSE)),
                     "masked"),
    "llm_full": (lambda: llm_dsfl._lanes(K, None, None, M), "full"),
    "llm_sparse": (lambda: llm_dsfl._lanes(K, WEIGHTS, None, M), "sparse"),
    "llm_topk": (lambda: llm_dsfl._lanes(K, WEIGHTS, MASK, M, topk=4),
                 "masked"),
}


def _same(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("case", list(CASES))
def test_lane_plan_decides_the_plane_and_its_operations(case):
    make, plane = CASES[case]
    lanes = make()
    rng = jax.random.PRNGKey(3)
    ctx = _ctx()
    tree = {"w": jnp.arange(K * 3, dtype=jnp.float32).reshape(K, 3),
            "n": jnp.arange(K, dtype=jnp.int32)}
    old = jax.tree.map(lambda a: -a - 1, tree)
    vals = jnp.linspace(0.5, 2.5, K, dtype=jnp.float32)
    assert (lanes.mask is None) == (plane == "full")
    assert (lanes.idx is None) == (plane != "sparse")

    if plane == "full":
        assert lanes.take(tree) is tree
        assert lanes.keep(tree, old) is tree
        assert lanes.put(tree, old) is tree
        assert lanes.zeros(tree) is tree
        _same(lanes.keys(rng, ctx), client_keys(rng, ctx, K))
        _same(lanes.mean(vals), jnp.mean(vals))
        return

    # the participants: the ctx mask, or the LLM round's nonzero weights
    np.testing.assert_array_equal(np.asarray(lanes.mask) > 0,
                                  np.asarray(MASK) > 0)
    if plane == "masked":
        assert lanes.take(tree) is tree
        assert lanes.put(tree, old) is tree
        assert lanes.zeros(tree) is tree
        _same(lanes.keep(tree, old), select_clients(lanes.mask, tree, old))
        _same(lanes.keys(rng, ctx), client_keys(rng, ctx, K))
        _same(lanes.mean(vals), masked_mean(vals, lanes.mask))
        return

    idx = active_indices(lanes.mask, M)
    _same(lanes.idx, idx)
    _same(lanes.lane_mask, jnp.take(lanes.mask, idx, axis=0))
    lane_tree = gather_clients(tree, idx)
    _same(lanes.take(tree), lane_tree)
    new = jax.tree.map(lambda a: a * 3 + 1, lane_tree)
    _same(lanes.keep(new, lane_tree),
          select_clients(jnp.take(lanes.mask, idx, axis=0), new, lane_tree))
    _same(lanes.put(new, old), scatter_clients(new, old, idx))
    _same(lanes.zeros(new),
          jax.tree.map(lambda a: scatter_zeros(a, K, idx), new))
    _same(lanes.keys(rng, ctx),
          jnp.take(client_keys(rng, ctx, K), idx, axis=0))
    vals_m = jnp.take(vals, idx, axis=0)
    _same(lanes.mean(vals_m),
          masked_mean(scatter_zeros(vals_m, K, idx), lanes.mask))
