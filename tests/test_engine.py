"""The unified FedAlgorithm/FedEngine API: golden parity against the seed
DSFLEngine, all three algorithms through one engine, typed-state
checkpointing, and the chunked open-batch inference path."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.algorithms import (BatchCtx, DSFLAlgorithm, FDAlgorithm,
                                   FDConfig, FedAvgAlgorithm, FedAvgConfig,
                                   RoundState)
from repro.core.client import predict_probs
from repro.core.engine import FedEngine, make_eval_fn
from repro.core.protocol import DSFLConfig, DSFLEngine
from repro.core.protocol import make_eval_fn as seed_make_eval_fn
from repro.data.pipeline import build_image_task
from repro.models.smallnets import apply_mnist_cnn, init_mnist_cnn

K = 4


def _init(k):
    return init_mnist_cnn(k, image_hw=16, widths=(8, 16), fc=32)


@pytest.fixture(scope="module")
def task():
    return build_image_task(seed=0, K=K, n_private=320, n_open=160,
                            n_test=160, distribution="non_iid")


@pytest.fixture(scope="module")
def client_params(rng):
    wg, sg = _init(rng)
    wk = jax.vmap(lambda k: _init(k)[0])(jax.random.split(rng, K))
    sk = jax.vmap(lambda k: _init(k)[1])(jax.random.split(rng, K))
    return wk, sk, wg, sg


HP = DSFLConfig(rounds=2, local_epochs=1, distill_epochs=1, batch_size=40,
                open_batch=80, aggregation="era")


# ------------------------------------------------------------ golden parity --
def test_fedengine_dsfl_matches_seed_engine_bitwise(task, client_params):
    """The redesigned engine must reproduce the reference DSFLEngine metrics
    bit-for-bit on a fixed seed (same ops, same RNG splits, same jit)."""
    wk, sk, wg, sg = client_params
    seed_eng = DSFLEngine(apply_mnist_cnn, HP,
                          seed_make_eval_fn(apply_mnist_cnn, task.x_test,
                                            task.y_test))
    seed_eng.run(wk, sk, wg, sg, task.x_clients, task.y_clients, task.open_x)

    algo = DSFLAlgorithm(apply_mnist_cnn, HP)
    eng = FedEngine(algo, make_eval_fn(apply_mnist_cnn, task.x_test,
                                       task.y_test))
    state = algo.init_from(wk, sk, wg, sg)
    eng.run(state, task)

    assert len(seed_eng.history) == len(eng.history) == HP.rounds
    for a, b in zip(seed_eng.history, eng.history):
        assert set(a) == set(b)
        for key in a:
            assert a[key] == b[key], f"{key}: {a[key]} != {b[key]}"


# ------------------------------------------- all three algorithms, one loop --
def test_fd_through_fedengine_improves(task, client_params):
    wk, sk, _, _ = client_params
    algo = FDAlgorithm(apply_mnist_cnn,
                       FDConfig(rounds=3, local_epochs=1, batch_size=40,
                                gamma=0.1, n_classes=task.n_classes))
    eng = FedEngine(algo, make_eval_fn(apply_mnist_cnn, task.x_test,
                                       task.y_test))
    eng.run(algo.init_from(wk, sk), task)
    accs = [h["test_acc"] for h in eng.history]
    # FD under strong non-IID is a weak learner (paper Fig. 2/5): just above
    # the 10% chance level at this micro scale, and improving
    assert accs[-1] > 0.12, accs
    assert accs[-1] > accs[0]
    # the non-scalar per-class logit table is exposed on last_metrics
    tg = eng.last_metrics["global_logit"]
    assert tg.shape == (task.n_classes, task.n_classes)
    np.testing.assert_allclose(np.sum(np.asarray(tg), -1), 1.0, atol=1e-4)


def test_fedavg_through_fedengine_improves(task, rng):
    w0, s0 = _init(rng)
    algo = FedAvgAlgorithm(apply_mnist_cnn,
                           FedAvgConfig(rounds=5, local_epochs=2,
                                        batch_size=40))
    eng = FedEngine(algo, make_eval_fn(apply_mnist_cnn, task.x_test,
                                       task.y_test))
    eng.run(algo.init_from(w0, s0), task, weights=jnp.ones((K,)))
    accs = [h["test_acc"] for h in eng.history]
    assert accs[-1] > 0.3, accs
    assert accs[-1] >= accs[0]


def test_on_round_hook_can_rewrite_state(task, rng):
    """The un-jitted between-round hook (attack injection etc.)."""
    import dataclasses
    w0, s0 = _init(rng)
    algo = FedAvgAlgorithm(apply_mnist_cnn,
                           FedAvgConfig(rounds=1, local_epochs=1,
                                        batch_size=40))
    frozen_w, frozen_s = _init(jax.random.fold_in(rng, 7))

    def on_round(r, state):
        return dataclasses.replace(state, server=dataclasses.replace(
            state.server, params=frozen_w, model_state=frozen_s))

    eng = FedEngine(algo, on_round=on_round)
    out = eng.run(algo.init_from(w0, s0), task)
    for a, b in zip(jax.tree.leaves(out.server.params),
                    jax.tree.leaves(frozen_w)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_compiled_hlo_covers_round_and_chunk_programs(task, client_params):
    """One program text per built round and fused chunk, lowered from the
    engine's own caches; building them again compiles nothing new."""
    wk, sk, wg, sg = client_params
    hp = DSFLConfig(rounds=1, local_epochs=1, distill_epochs=1, batch_size=40,
                    open_batch=80, aggregation="era")
    eng = FedEngine(DSFLAlgorithm(apply_mnist_cnn, hp, use_kernel=True))
    state = eng.algo.init_from(wk, sk, wg, sg)
    assert eng.compiled_hlo(state, task) == []
    state = eng.run(state, task, rounds=1)
    state = eng.run(state, task, rounds=2, chunk_rounds=2)
    texts = eng.compiled_hlo(state, task)
    assert len(texts) == 2
    assert texts[0].startswith("HloModule jit_round")
    assert texts[1].startswith("HloModule jit_chunk_fn")
    counts = eng.compile_counts()
    eng.compiled_hlo(state, task)
    assert eng.compile_counts() == counts


# ------------------------------------------------- adaptive weighted-ERA ----
def test_weighted_era_learns_to_downweight_label_flipped_client(task,
                                                                client_params):
    """agg_weights=None + aggregation="weighted_era" re-estimates the
    reliability weights every round from the inverse entropy of each
    client's uploaded soft labels (ROADMAP open item, paper §5 "future
    work"): a label-flipped attacker — whose flipped supervision on non-IID
    shards yields wrong *and* diffuse open-set predictions — must end up
    below every honest client, where the old static vector stayed
    uniform."""
    import dataclasses
    wk, sk, wg, sg = client_params
    C = task.n_classes

    def corrupt(probs, xo, rng):
        flipped = jnp.roll(probs[0], 1, axis=-1)     # class-permuted ...
        attacked = 0.5 * flipped + 0.5 / C           # ... and diffuse
        return probs.at[0].set(attacked)

    hp = dataclasses.replace(HP, aggregation="weighted_era")
    algo = DSFLAlgorithm(apply_mnist_cnn, hp, corrupt=corrupt)
    eng = FedEngine(algo)
    eng.run(algo.init_from(wk, sk, wg, sg), task, rounds=2)
    w = np.asarray(eng.last_metrics["agg_weights"])
    np.testing.assert_allclose(w.sum(), 1.0, atol=1e-5)
    assert w[0] < w[1:].min(), w

    # a static agg_weights vector still short-circuits the adaptation
    static = DSFLAlgorithm(apply_mnist_cnn, hp, corrupt=corrupt,
                           agg_weights=jnp.ones((K,)))
    eng2 = FedEngine(static)
    eng2.run(static.init_from(wk, sk, wg, sg), task, rounds=1)
    w2 = np.asarray(eng2.last_metrics["agg_weights"])
    np.testing.assert_allclose(w2, np.full(K, 1 / K), atol=1e-6)


# ------------------------------------------------------------ checkpointing --
def test_state_checkpoint_roundtrip(task, client_params, tmp_path):
    wk, sk, wg, sg = client_params
    algo = DSFLAlgorithm(apply_mnist_cnn, HP)
    eng = FedEngine(algo)
    state = eng.run(algo.init_from(wk, sk, wg, sg), task, rounds=1)
    path = os.path.join(tmp_path, "state.msgpack")
    eng.save_state(path, state)
    restored = eng.load_state(path, algo.init_from(wk, sk, wg, sg))
    assert isinstance(restored, RoundState)
    for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_resume_continues_rng_stream(task, client_params, tmp_path):
    """save -> load -> run(start_round=n) must reproduce an uninterrupted
    run exactly: same open-batch draws, same round keys, same metrics."""
    wk, sk, wg, sg = client_params
    algo = DSFLAlgorithm(apply_mnist_cnn, HP)
    full = FedEngine(algo)
    full.run(algo.init_from(wk, sk, wg, sg), task, rounds=2)

    first = FedEngine(algo)
    mid = first.run(algo.init_from(wk, sk, wg, sg), task, rounds=1)
    path = os.path.join(tmp_path, "mid.msgpack")
    first.save_state(path, mid)
    second = FedEngine(algo)
    restored = second.load_state(path, algo.init_from(wk, sk, wg, sg))
    second.run(restored, task, rounds=1, start_round=1)

    assert [h["round"] for h in full.history] == [1, 2]
    # load_state restored round 1's history record; the resumed round
    # appended round 2's — identical to the uninterrupted run's
    assert [h["round"] for h in second.history] == [1, 2]
    for key in full.history[1]:
        assert full.history[1][key] == second.history[1][key], key


def test_resume_without_hand_tracked_start_round(task, client_params,
                                                 tmp_path):
    """load_state restores rounds_done + history, so a plain run() resumes
    the RNG stream — no caller-side start_round bookkeeping."""
    wk, sk, wg, sg = client_params
    algo = DSFLAlgorithm(apply_mnist_cnn, HP)
    full = FedEngine(algo)
    full.run(algo.init_from(wk, sk, wg, sg), task, rounds=2)

    first = FedEngine(algo)
    mid = first.run(algo.init_from(wk, sk, wg, sg), task, rounds=1)
    path = os.path.join(tmp_path, "mid.msgpack")
    first.save_state(path, mid)
    second = FedEngine(algo)
    restored = second.load_state(path, algo.init_from(wk, sk, wg, sg))
    assert second.rounds_done == 1
    assert second.history == first.history
    second.run(restored, task, rounds=1)
    assert second.history == full.history


def test_history_accepts_python_scalar_metrics(task, rng):
    """The history writer must not assume metrics are jax arrays: a plain
    Python float (e.g. from an un-jitted round) used to raise
    AttributeError on .ndim."""
    w0, s0 = _init(rng)
    algo = FedAvgAlgorithm(apply_mnist_cnn,
                           FedAvgConfig(rounds=1, local_epochs=1,
                                        batch_size=40))
    eng = FedEngine(algo)
    state = algo.init_from(w0, s0)
    eng._round = lambda s, c, k: (s, {"py_metric": 0.5,
                                      "vec": jnp.zeros((3,))})
    eng.run(state, task, rounds=1)
    assert eng.history[0]["py_metric"] == 0.5
    assert "vec" not in eng.history[0]


def test_checkpoint_rejects_wrong_algorithm(task, client_params, tmp_path):
    wk, sk, wg, sg = client_params
    dsfl = FedEngine(DSFLAlgorithm(apply_mnist_cnn, HP))
    state = dsfl.algo.init_from(wk, sk, wg, sg)
    path = os.path.join(tmp_path, "state.msgpack")
    dsfl.save_state(path, state)
    fd = FedEngine(FDAlgorithm(apply_mnist_cnn, FDConfig(rounds=1)))
    with pytest.raises(ValueError, match="dsfl"):
        fd.load_state(path, state)


# ----------------------------------------------------- states are pytrees ----
def test_round_state_is_a_pytree(client_params):
    wk, sk, wg, sg = client_params
    algo = DSFLAlgorithm(apply_mnist_cnn, HP)
    state = algo.init_from(wk, sk, wg, sg)
    doubled = jax.tree.map(lambda a: a * 2, state)
    assert isinstance(doubled, RoundState)
    np.testing.assert_allclose(np.asarray(jax.tree.leaves(doubled)[0]),
                               2 * np.asarray(jax.tree.leaves(state)[0]))
    # BatchCtx with absent slots contributes only the present leaves
    ctx = BatchCtx(x=jnp.zeros((2, 3)))
    assert len(jax.tree.leaves(ctx)) == 1


# ------------------------------------------------- chunked open inference ----
def test_predict_probs_chunked_matches_full(task, client_params):
    wk, sk, _, _ = client_params
    w = jax.tree.map(lambda a: a[0], wk)
    s = jax.tree.map(lambda a: a[0], sk)
    full = predict_probs(apply_mnist_cnn, w, s, task.open_x)
    for bs in (32, 50, 160, 1000):   # divides n, ragged tail, ==n, >n
        chunked = predict_probs(apply_mnist_cnn, w, s, task.open_x,
                                batch_size=bs)
        assert chunked.shape == full.shape
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(full),
                                   atol=1e-6)
