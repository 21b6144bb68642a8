"""Cohort-resident federation: the million-client refactor's parity pins.

Layer by layer, the cohort path must reproduce the dense path *bitwise* at
small K: `core.prng.split_take` rows equal the dense key split's rows,
`init_cohort` slabs equal rows of the dense init stack, a lazily-filled
`ClientStore` equals the up-front store, and a full `CohortRunner` run
(O(m) slabs, id-keyed host store, per-id data provider) equals the dense
masked engine fed the same densified plans — state, touched client rows
and history floats.  Two-level ERA (`core.hierarchy`) carries the split
contract: bitwise at ``n_edges=1``, pinned tolerance with exact zero-lane
behaviour at every deeper level."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation as agg
from repro.core.algorithms import DSFLAlgorithm, FDAlgorithm, FDConfig
from repro.core.cohort import ClientStore, build_slab, slab_ctx_plan
from repro.core.engine import FedEngine
from repro.core.hierarchy import (edge_shards, hierarchical_weighted_era,
                                  hierarchical_weighted_sa)
from repro.core.prng import split_take
from repro.core.protocol import DSFLConfig
from repro.data.pipeline import ArrayProvider, SyntheticProvider, \
    build_image_task
from repro.models.smallnets import apply_tiny_mlp, init_tiny_mlp
from repro.sim import (AsyncBufferScheduler, ClientPopulation, CohortRunner,
                       SimRunner, SyncScheduler)

K = 6
HP = DSFLConfig(rounds=4, local_epochs=1, distill_epochs=1, batch_size=20,
                open_batch=40, aggregation="era")


def _init(k):
    return init_tiny_mlp(k)


@pytest.fixture(scope="module")
def task():
    return build_image_task(seed=0, K=K, n_private=240, n_open=80, n_test=40,
                            distribution="non_iid")


def _assert_trees_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


# ------------------------------------------------------- split_take (prng) ---
@pytest.mark.parametrize("num", [1, 2, 5, 8, 33, 1000])
def test_split_take_rows_match_dense_split_bitwise(num):
    """The counter-mode pin: any row subset of ``split(key, num)`` — odd and
    even num, duplicated and unsorted ids — computed in O(m)."""
    key = jax.random.PRNGKey(7)
    dense = np.asarray(jax.random.split(key, num))
    ids = np.array([0, num - 1, num // 2, 0], np.int64) % num
    got = np.asarray(split_take(key, ids, num))
    np.testing.assert_array_equal(got, dense[ids])
    allrows = np.asarray(split_take(key, np.arange(num), num))
    np.testing.assert_array_equal(allrows, dense)


def test_split_take_hypothesis_any_ids():
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    @given(st.integers(1, 600), st.data(), st.integers(0, 2**31 - 1))
    @settings(deadline=None, max_examples=25)
    def check(num, data, seed):
        ids = np.asarray(data.draw(st.lists(st.integers(0, num - 1),
                                            min_size=1, max_size=16)),
                         np.int64)
        key = jax.random.PRNGKey(seed)
        np.testing.assert_array_equal(
            np.asarray(split_take(key, ids, num)),
            np.asarray(jax.random.split(key, num))[ids])

    check()


def test_split_take_typed_key_falls_back_and_matches():
    """Non-raw keys (typed PRNG keys) take the dense-split fallback — same
    rows, just without the O(m) shortcut."""
    key = jax.random.key(3)      # typed key
    ids = np.array([4, 1, 1], np.int64)
    got = split_take(key, ids, 9)
    want = jax.random.split(key, 9)[jnp.asarray(ids)]
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(got)),
        np.asarray(jax.random.key_data(want)))


def test_split_take_non_partitionable_layout_falls_back_and_matches():
    """Under ``jax_threefry_partitionable=False`` a row of the split is not
    its own counter's encryption: the dense fallback keeps the rows exact."""
    key = jax.random.PRNGKey(7)
    ids = np.array([4, 1, 1, 0], np.int64)
    with jax.threefry_partitionable(False):
        got = np.asarray(split_take(key, ids, 5))
        want = np.asarray(jax.random.split(key, 5))[ids]
    np.testing.assert_array_equal(got, want)


# ------------------------------------------------------ lazy init parity -----
def test_init_cohort_rows_match_dense_init_stack(task):
    """Client g's fresh state is a function of (rng, g) alone: slab rows
    equal rows of the dense `_stack_init` stack, in any order, any subset."""
    algo = DSFLAlgorithm(apply_tiny_mlp, HP)
    rng = jax.random.PRNGKey(HP.seed)
    dense = algo.init(rng, _init, task).clients
    for ids in ([2, 5], [5, 0, 3], list(range(K))):
        slab = algo.init_cohort(rng, _init, np.asarray(ids, np.int64), K)
        for la, lb in zip(jax.tree.leaves(slab), jax.tree.leaves(dense)):
            np.testing.assert_array_equal(np.asarray(la),
                                          np.asarray(lb)[np.asarray(ids)])


def test_client_store_lazy_fill_scatter_roundtrip(task):
    algo = DSFLAlgorithm(apply_tiny_mlp, HP)
    rng = jax.random.PRNGKey(HP.seed)
    store = ClientStore(lambda ids: algo.init_cohort(rng, _init, ids, K))
    assert len(store) == 0 and store.resident_bytes() == 0

    slab = store.gather(np.array([4, 1, 4]))      # duplicates allowed
    assert len(store) == 2
    dense = algo.init(rng, _init, task).clients
    for la, lb in zip(jax.tree.leaves(slab), jax.tree.leaves(dense)):
        np.testing.assert_array_equal(np.asarray(la),
                                      np.asarray(lb)[[4, 1, 4]])

    # scatter honours n_real: the pad lane (repeat of id 4) must not clobber
    mutated = jax.tree.map(lambda l: l + 1.0, slab)
    store.scatter(np.array([4, 1, 4]), mutated, n_real=2)
    back = store.gather(np.array([1, 4]))
    for la, lb in zip(jax.tree.leaves(back), jax.tree.leaves(dense)):
        np.testing.assert_array_equal(np.asarray(la),
                                      np.asarray(lb)[[1, 4]] + 1.0)
    assert store.resident_bytes() > 0


def test_client_store_save_load_roundtrip(task, tmp_path):
    algo = DSFLAlgorithm(apply_tiny_mlp, HP)
    rng = jax.random.PRNGKey(HP.seed)
    store = ClientStore(lambda ids: algo.init_cohort(rng, _init, ids, K))
    store.gather(np.array([0, 3, 5]))
    path = os.path.join(tmp_path, "clients.store")
    store.save(path)
    fresh = ClientStore(lambda ids: algo.init_cohort(rng, _init, ids, K))
    fresh.load(path)
    assert list(fresh.ids()) == [0, 3, 5]
    _assert_trees_equal(store.gather(np.array([0, 3, 5])),
                        fresh.gather(np.array([0, 3, 5])))


# ----------------------------------------------------------- slab planning ---
def test_build_slab_union_pad_and_overflow():
    ids, n_real = build_slab([np.array([4, 2]), np.array([2, 7])], 5)
    np.testing.assert_array_equal(ids, [2, 4, 7, 2, 2])
    assert n_real == 3
    with pytest.raises(ValueError, match="slab_size"):
        build_slab([np.arange(6)], 5)


def test_slab_ctx_plan_lanes_match_dense_mask():
    from repro.sim import CohortPlan
    p0 = CohortPlan(np.array([2, 7]), np.array([0, 1]), 0.0, 1.0,
                    np.zeros(0, np.int64))
    p1 = CohortPlan(np.array([4]), np.array([0]), 1.0, 2.0,
                    np.zeros(0, np.int64))
    slab_ids, n_real = build_slab([p0.ids, p1.ids], 5)
    plan = slab_ctx_plan([p0, p1], slab_ids, n_real)
    np.testing.assert_array_equal(plan["mask"],
                                  [[1, 0, 1, 0, 0], [0, 1, 0, 0, 0]])
    np.testing.assert_array_equal(plan["stale"],
                                  [[0, 0, 1, 0, 0], [0, 0, 0, 0, 0]])


# --------------------------------------------------------- two-level ERA -----
def _prob_stack(seed, k=8, n=4, c=10):
    logits = jax.random.normal(jax.random.PRNGKey(seed), (k, n, c)) * 3
    return jax.nn.softmax(logits, -1)


def test_edge_shards_partition_properties():
    for k, n in [(8, 1), (8, 3), (7, 7), (10, 4)]:
        bounds = edge_shards(k, n)
        sizes = [e - s for s, e in bounds]
        assert bounds[0][0] == 0 and bounds[-1][1] == k
        assert all(b[0] == a[1] for a, b in zip(bounds, bounds[1:]))
        assert max(sizes) - min(sizes) <= 1
    with pytest.raises(ValueError):
        edge_shards(4, 5)
    with pytest.raises(ValueError):
        edge_shards(4, 0)


def test_hierarchy_single_edge_is_bitwise_flat():
    """The parity anchor: n_edges=1 IS the flat path, bit for bit."""
    p = _prob_stack(0)
    w = jnp.asarray([0.0, 2.0, 1.0, 0.0, 3.0, 1.0, 0.5, 0.0])
    np.testing.assert_array_equal(
        np.asarray(hierarchical_weighted_sa(p, w, n_edges=1)),
        np.asarray(agg.weighted_sa(p, w)))
    np.testing.assert_array_equal(
        np.asarray(hierarchical_weighted_era(p, w, 0.1, n_edges=1)),
        np.asarray(agg.weighted_era(p, w, 0.1)))


@pytest.mark.parametrize("n_edges", [2, 3, 4, 8])
def test_hierarchy_depth_tolerance_contract(n_edges):
    """Deeper trees re-associate the cross-client sum: equality degrades
    from bitwise to a pinned ~1e-6 tolerance — never worse."""
    p = _prob_stack(1)
    w = jnp.asarray(np.random.default_rng(1).random(8).astype(np.float32))
    flat_sa = np.asarray(agg.weighted_sa(p, w))
    flat_era = np.asarray(agg.weighted_era(p, w, 0.1))
    np.testing.assert_allclose(
        np.asarray(hierarchical_weighted_sa(p, w, n_edges=n_edges)),
        flat_sa, rtol=1e-6, atol=1e-7)
    np.testing.assert_allclose(
        np.asarray(hierarchical_weighted_era(p, w, 0.1, n_edges=n_edges)),
        flat_era, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("n_edges", [1, 2, 3, 8])
def test_hierarchy_zero_weight_lanes_exact_at_any_depth(n_edges):
    """What stays *exact* under re-association: a zero-weight lane
    contributes exactly nothing inside whichever edge shard it falls —
    replacing its probs with garbage cannot change a single output bit.
    This is the masking/sparse-plane guarantee surviving the hierarchy."""
    p = _prob_stack(2)
    w = jnp.asarray([0.0, 2.0, 0.0, 1.0, 3.0, 0.0, 0.5, 1.0])
    garbage = p.at[jnp.asarray([0, 2, 5])].set(123.456)
    for fn in (lambda x: hierarchical_weighted_sa(x, w, n_edges=n_edges),
               lambda x: hierarchical_weighted_era(x, w, 0.1,
                                                   n_edges=n_edges)):
        np.testing.assert_array_equal(np.asarray(fn(p)),
                                      np.asarray(fn(garbage)))


def test_hierarchy_kernel_route_matches_einsum():
    """Each edge's partial through the fused Pallas weighted-mean kernel
    (interpret mode — no accelerator needed): tolerance vs the einsum tree,
    and the n_edges=1 kernel route is exactly the flat kernel route."""
    p = _prob_stack(3)
    w = jnp.asarray(np.random.default_rng(3).random(8).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(hierarchical_weighted_sa(p, w, n_edges=1, use_kernel=True,
                                            interpret=True)),
        np.asarray(agg.weighted_sa(p, w, use_kernel=True, interpret=True)))
    np.testing.assert_allclose(
        np.asarray(hierarchical_weighted_sa(p, w, n_edges=4, use_kernel=True,
                                            interpret=True)),
        np.asarray(agg.weighted_sa(p, w)), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("agg_edges", [2, 3])
def test_dsfl_round_with_edge_tree_close_to_flat(task, agg_edges):
    """A full DSFL round aggregated through the edge tree stays within
    float tolerance of the flat round's server params after one round."""
    flat = FedEngine(DSFLAlgorithm(apply_tiny_mlp, HP))
    s1 = flat.run(flat.init(_init, task), task, rounds=1)
    algo = DSFLAlgorithm(apply_tiny_mlp, HP, agg_edges=agg_edges)
    eng = FedEngine(algo)
    s2 = eng.run(eng.init(_init, task), task, rounds=1)
    for a, b in zip(jax.tree.leaves(s1.server), jax.tree.leaves(s2.server)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


# -------------------------------------------- cohort runner golden parity ----
def _dense_plan(sched_args, rounds, up, down, seed=0):
    """Replay the cohort schedule's draws and densify them into a ctx_plan
    for the dense engine — CohortPlan.dense_mask/.dense_staleness are the
    bridge (cohort draws differ from next_round's, so parity is defined
    against the *same* realized plans, not a parallel dense scheduler)."""
    sched = SyncScheduler(**sched_args)
    plans = [sched.next_cohort(np.random.default_rng([seed, i]), up, down)
             for i in range(rounds)]
    mask = jnp.asarray(np.stack([p.dense_mask(K) for p in plans]),
                       jnp.float32)
    stale = jnp.asarray(np.stack([p.dense_staleness(K) for p in plans]),
                        jnp.int32)
    return plans, {"mask": mask, "stale": stale}


@pytest.mark.parametrize("aggregation,sched_kw", [
    ("era", dict(fraction=0.5, deadline=3.0, straggler="admit")),
    ("weighted_era", dict(fraction=0.34, deadline=None, straggler="drop")),
])
def test_cohort_runner_bitwise_identical_to_dense_masked(task, aggregation,
                                                         sched_kw):
    """THE acceptance pin: a CohortRunner round — id-keyed host store, O(m)
    slab, cohort keys, slab ctx plan — is bitwise the dense masked round
    fed the same densified plans: server state, every touched client's
    stored rows, and the engine's history floats."""
    hp = dataclasses.replace(HP, aggregation=aggregation)
    algo = DSFLAlgorithm(apply_tiny_mlp, hp)
    rng0 = jax.random.PRNGKey(hp.seed)
    pop = ClientPopulation.lognormal(1, K)

    eng_c = FedEngine(algo)
    store = ClientStore(lambda ids: algo.init_cohort(rng0, _init, ids, K))
    runner = CohortRunner(engine=eng_c,
                          scheduler=SyncScheduler(pop, **sched_kw),
                          provider=ArrayProvider(task), store=store, seed=0)
    s_c = runner.run(algo.init_server(rng0, _init), rounds=4, chunk_rounds=2)

    up, down = runner._leg_bytes
    _, plan = _dense_plan(dict(population=pop, **sched_kw), 4, up, down)
    eng_d = FedEngine(algo)
    s_d = eng_d.run(eng_d.init(_init, task), task, rounds=4, chunk_rounds=2,
                    ctx_plan=plan)

    _assert_trees_equal(s_c.server, s_d.server)
    dense_clients = jax.device_get(s_d.clients)
    for cid in store.ids():
        row = store.gather(np.array([cid]))
        for la, lb in zip(jax.tree.leaves(row),
                          jax.tree.leaves(dense_clients)):
            np.testing.assert_array_equal(np.asarray(la)[0],
                                          np.asarray(lb)[int(cid)],
                                          err_msg=f"client {cid}")
    dense_hist = {r["round"]: r for r in eng_d.history}
    cohort_hist = {r["round"]: r for r in runner.history.records}
    for rnd, rec in dense_hist.items():
        for key, v in rec.items():
            if isinstance(v, float):
                assert cohort_hist[rnd][key] == v, (rnd, key)
    assert runner.peak_slab_bytes > 0


def test_cohort_runner_fd_matches_dense(task):
    """FD (no server model, empty init_server) through the cohort plane."""
    hp = FDConfig(rounds=3, local_epochs=1, batch_size=20, gamma=0.1,
                  n_classes=task.n_classes)
    algo = FDAlgorithm(apply_tiny_mlp, hp)
    rng0 = jax.random.PRNGKey(hp.seed)
    pop = ClientPopulation.lognormal(1, K)
    kw = dict(fraction=0.5, deadline=3.0, straggler="admit")

    eng_c = FedEngine(algo)
    store = ClientStore(lambda ids: algo.init_cohort(rng0, _init, ids, K))
    runner = CohortRunner(engine=eng_c, scheduler=SyncScheduler(pop, **kw),
                          provider=ArrayProvider(task), store=store, seed=0)
    runner.run(algo.init_server(rng0, _init), rounds=3, chunk_rounds=3)

    up, down = runner._leg_bytes
    _, plan = _dense_plan(dict(population=pop, **kw), 3, up, down)
    eng_d = FedEngine(algo)
    s_d = eng_d.run(eng_d.init(_init, task), task, rounds=3, chunk_rounds=3,
                    ctx_plan=plan)
    dense_clients = jax.device_get(s_d.clients)
    for cid in store.ids():
        row = store.gather(np.array([cid]))
        for la, lb in zip(jax.tree.leaves(row),
                          jax.tree.leaves(dense_clients)):
            np.testing.assert_array_equal(np.asarray(la)[0],
                                          np.asarray(lb)[int(cid)])


def test_synthetic_provider_rows_are_id_deterministic():
    """slab(ids) row j depends on ids[j] alone — any order, any cohort."""
    prov = SyntheticProvider(seed=0, n_clients=1000, n_per_client=8,
                             n_open=16, n_test=4)
    a = prov.slab(np.array([999, 3, 41]))
    b = prov.slab(np.array([3, 999]))
    np.testing.assert_array_equal(np.asarray(a.x_clients)[1],
                                  np.asarray(b.x_clients)[0])
    np.testing.assert_array_equal(np.asarray(a.x_clients)[0],
                                  np.asarray(b.x_clients)[1])
    assert a.open_x is b.open_x        # shared open set materializes once


# ------------------------------------------------- async cohort scheduler ----
def test_async_next_cohort_matches_next_round_without_jitter():
    """With zero jitter the arrival process is deterministic, so the heap
    form must realize exactly the dense argsort form's rounds — ids,
    staleness, clock — on separate instances of the same fleet."""
    def pop():
        lat = np.array([1.0, 3.5, 1.0, 2.0])
        inf = np.full_like(lat, np.inf)
        return ClientPopulation(lat, inf, inf, np.ones_like(lat))

    dense = AsyncBufferScheduler(pop(), buffer_size=2)
    heap = AsyncBufferScheduler(pop(), buffer_size=2)
    for r in range(6):
        rp = dense.next_round(np.random.default_rng(r), 0, 0)
        cp = heap.next_cohort(np.random.default_rng(r), 0, 0)
        np.testing.assert_array_equal(cp.ids, np.flatnonzero(rp.mask))
        np.testing.assert_array_equal(cp.staleness, rp.staleness[cp.ids])
        assert cp.t_end == rp.t_end
    assert dense.clock.now == heap.clock.now


def test_async_scheduler_state_roundtrip_includes_heap():
    pop = ClientPopulation.lognormal(2, 5, compute_sigma=0.8)
    sched = AsyncBufferScheduler(pop, buffer_size=2, jitter_sigma=0.2)
    for r in range(3):
        sched.next_cohort(np.random.default_rng(r), 10.0, 10.0)
    clone = AsyncBufferScheduler(pop, buffer_size=2, jitter_sigma=0.2)
    clone.set_state(sched.state())
    for r in range(3, 6):
        a = sched.next_cohort(np.random.default_rng(r), 10.0, 10.0)
        b = clone.next_cohort(np.random.default_rng(r), 10.0, 10.0)
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.staleness, b.staleness)
        assert a.t_end == b.t_end


def test_cohort_runner_async_matches_simrunner(task):
    """Async cohort rounds (heap scheduler, slab engine) against the dense
    `SimRunner` async path — same realized rounds at jitter 0, so server
    state and history must agree bitwise."""
    algo = DSFLAlgorithm(apply_tiny_mlp, HP)
    rng0 = jax.random.PRNGKey(HP.seed)

    def pop():
        lat = np.array([1.0, 3.5, 1.0, 2.0, 1.5, 2.5])
        inf = np.full_like(lat, np.inf)
        return ClientPopulation(lat, inf, inf, np.ones_like(lat))

    eng_c = FedEngine(algo)
    store = ClientStore(lambda ids: algo.init_cohort(rng0, _init, ids, K))
    runner = CohortRunner(engine=eng_c,
                          scheduler=AsyncBufferScheduler(pop(),
                                                         buffer_size=2),
                          provider=ArrayProvider(task), store=store, seed=0)
    s_c = runner.run(algo.init_server(rng0, _init), rounds=3)

    eng_d = FedEngine(algo)
    sim = SimRunner(eng_d, AsyncBufferScheduler(pop(), buffer_size=2),
                    seed=0)
    s_d = sim.run(eng_d.init(_init, task), task, rounds=3)
    _assert_trees_equal(s_c.server, s_d.server)
    dense_clients = jax.device_get(s_d.clients)
    for cid in store.ids():
        row = store.gather(np.array([cid]))
        for la, lb in zip(jax.tree.leaves(row),
                          jax.tree.leaves(dense_clients)):
            np.testing.assert_array_equal(np.asarray(la)[0],
                                          np.asarray(lb)[int(cid)])
