"""Pod-scale LLM algorithms on the unified FedAlgorithm/FedEngine API:
golden parity against the raw `llm_dsfl` round steps (bit-for-bit — the CI
tier-1 job runs this on 8 fake CPU devices), mesh-aware engine jit with
`launch.sharding` placements, wire/comm parity of the top-k LLM payload, and
checkpoint resume without hand-tracked round counters."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import wire
from repro.core.comm import CommModel
from repro.core.engine import FedEngine
from repro.core.llm_algorithms import (LLMDSFLAlgorithm, LLMFedAvgAlgorithm,
                                       LLMFedAvgHP)
from repro.core.aggregation import sa
from repro.core.llm_dsfl import (LLMDsflHP, dsfl_exchange, dsfl_round_step,
                                 fedavg_round_step)
from repro.data.pipeline import build_lm_task
from repro.models.api import model_init
from repro.models.shardctx import axis_ctx

CFG = get_config("qwen1.5-4b").smoke()
K, B, S = 2, 4, 32


@pytest.fixture(scope="module")
def task():
    return build_lm_task(seed=0, K=K, batch=B, seq=S, vocab=CFG.vocab)


@pytest.fixture(scope="module")
def stacked(rng):
    return jax.vmap(lambda k: model_init(CFG, k))(jax.random.split(rng, K))


def _engine_open_batch(hp, task):
    """Replicate FedEngine's round-0 RNG stream: the o_r draw."""
    rng = jax.random.PRNGKey(hp.seed)
    _, _, ri = jax.random.split(rng, 3)
    n_open = jax.tree.leaves(task.open_x)[0].shape[0]
    n_r = min(hp.open_batch, n_open)
    return jax.random.choice(ri, n_open, (n_r,), replace=False)


# ------------------------------------------------------------ golden parity --
def test_llm_dsfl_engine_matches_round_step_bitwise(task, stacked):
    """One engine round must equal the raw dsfl_round_step exactly (same
    gather, same ops, same jit) — the LLM analogue of the DSFLEngine
    golden-parity pin."""
    hp = LLMDsflHP(lr=5e-3, rounds=1, seed=0, open_batch=B)
    algo = LLMDSFLAlgorithm(CFG, hp)
    eng = FedEngine(algo)
    out = eng.run(algo.init_from(stacked), task, rounds=1)

    o_idx = _engine_open_batch(hp, task)
    ref, ref_loss = jax.jit(
        lambda p, pb, ox, oi: dsfl_round_step(
            CFG, p, pb, jax.tree.map(lambda a: jnp.take(a, oi, axis=0), ox),
            hp))(stacked, task.x_clients, task.open_x, o_idx)
    for a, b in zip(jax.tree.leaves(out.clients.params), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert eng.history[0]["loss"] == float(ref_loss)


def test_llm_fedavg_engine_matches_round_step_bitwise(task, stacked):
    algo = LLMFedAvgAlgorithm(CFG, LLMFedAvgHP(lr=1e-3, rounds=1))
    eng = FedEngine(algo)
    out = eng.run(algo.init_from(stacked), task, rounds=1)
    ref, _ = jax.jit(
        lambda p, pb: fedavg_round_step(CFG, p, pb, 1e-3))(
        stacked, task.x_clients)
    for a, b in zip(jax.tree.leaves(out.clients.params), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the round's broadcast synced the clients
    for leaf in jax.tree.leaves(out.clients.params):
        np.testing.assert_allclose(np.asarray(leaf[0], np.float32),
                                   np.asarray(leaf[1], np.float32), atol=1e-6)


# --------------------------------------------------- mesh-aware engine jit ---
def _pod_mesh():
    from repro.launch.mesh import make_client_mesh
    return make_client_mesh(K)


def test_llm_dsfl_sharded_engine_round_runs(task, stacked, tmp_path):
    """End-to-end through FedEngine(mesh=...): in_shardings from
    algo.shardings (client axis on "pod"), donated state.  On the CI job this
    exercises 8 fake CPU devices; on one device the same code path runs on a
    (1, 1, 1) mesh."""
    hp = LLMDsflHP(lr=5e-3, rounds=1, seed=0, open_batch=B)
    algo = LLMDSFLAlgorithm(CFG, hp)
    mesh = _pod_mesh()
    eng = FedEngine(algo, mesh=mesh, donate_state=True)
    state = algo.init_from(jax.tree.map(jnp.copy, stacked))
    with axis_ctx(mesh, batch_axes=("data",)):
        out = eng.run(state, task, rounds=1)
    assert np.isfinite(eng.history[0]["loss"])
    # msgpack checkpoint of the sharded state: restore straight onto shards
    path = os.path.join(tmp_path, "sharded.msgpack")
    eng.save_state(path, out)
    ctx = eng.make_ctx(task, o_idx=jnp.zeros((B,), jnp.int32))
    st_sh, _ = algo.shardings(mesh, out, ctx)
    restored = eng.load_state(path, algo.init_from(stacked), shardings=st_sh)
    for a, b in zip(jax.tree.leaves(out.clients.params),
                    jax.tree.leaves(restored.clients.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pod_size = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]
    if pod_size > 1:
        # the client-stacked params actually live on the pod axis
        sh = jax.tree.leaves(out.clients.params)[0].sharding
        assert "pod" in sh.spec
    # sharded result must agree with the unsharded reference
    o_idx = _engine_open_batch(hp, task)
    ref, _ = jax.jit(
        lambda p, pb, ox, oi: dsfl_round_step(
            CFG, p, pb, jax.tree.map(lambda a: jnp.take(a, oi, axis=0), ox),
            hp))(stacked, task.x_clients, task.open_x, o_idx)
    for a, b in zip(jax.tree.leaves(out.clients.params), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=1e-2)


def test_llm_dsfl_sharded_engine_chunked_scan_parity(task, stacked):
    """chunk_rounds composes with mesh= in_shardings + donate_state: two
    scanned rounds equal two per-round loop rounds bitwise (also pins the
    out_shardings fix — round 2 consumes round 1's output placement)."""
    hp = LLMDsflHP(lr=5e-3, rounds=2, seed=0, open_batch=B)
    algo = LLMDSFLAlgorithm(CFG, hp)
    mesh = _pod_mesh()

    def go(chunk):
        eng = FedEngine(algo, mesh=mesh, donate_state=True)
        state = algo.init_from(jax.tree.map(jnp.copy, stacked))
        with axis_ctx(mesh, batch_axes=("data",)):
            out = eng.run(state, task, rounds=2, chunk_rounds=chunk)
        return eng, out

    e1, o1 = go(1)
    e2, o2 = go(2)
    assert e1.history == e2.history
    for a, b in zip(jax.tree.leaves(o1.clients.params),
                    jax.tree.leaves(o2.clients.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    pod_size = dict(zip(mesh.axis_names, mesh.devices.shape))["pod"]
    if pod_size > 1:
        sh = jax.tree.leaves(o2.clients.params)[0].sharding
        assert "pod" in sh.spec


# ------------------------------------------------------- exchange probe ----
PROBE = (0, 5, S + 3, 3 * S - 1, B * S - 1)


@pytest.mark.parametrize("path", ["loop", "scan_overlap"])
def test_llm_dsfl_probe_reports_the_exchanged_mean(task, stacked, path):
    """``probe_rows`` reports the clients' float32 mean at those o_r tokens
    (what the exchange delivered, before ERA) and changes nothing else:
    parameters and losses bitwise as without it, on the per-round loop
    and on the pipelined scan."""
    rounds, kw = ((1, {}) if path == "loop"
                  else (2, dict(chunk_rounds=2, overlap=True)))
    hp = LLMDsflHP(lr=5e-3, rounds=rounds, seed=0, open_batch=B)

    def go(probe):
        algo = LLMDSFLAlgorithm(CFG, hp, probe_rows=probe)
        eng = FedEngine(algo)
        return eng, eng.run(algo.init_from(stacked), task, **kw)

    plain, out = go(None)
    probed, out_p = go(PROBE)
    assert probed.history == plain.history
    for a, b in zip(jax.tree.leaves(out.clients.params),
                    jax.tree.leaves(out_p.clients.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert "client_mean" not in plain.last_metrics
    got = np.asarray(probed.last_metrics["client_mean"])
    # the last round's exchange, recomputed from its input parameters
    params = stacked
    if rounds == 2:
        first = FedEngine(LLMDSFLAlgorithm(CFG, hp))
        params = first.run(first.algo.init_from(stacked), task,
                           rounds=1).clients.params
    rng = jax.random.PRNGKey(hp.seed)
    for _ in range(rounds):
        rng, _, ri = jax.random.split(rng, 3)
    o_idx = jax.random.choice(ri, B, (B,), replace=False)
    open_b = jax.tree.map(lambda a: jnp.take(a, o_idx, axis=0), task.open_x)
    (probs,) = dsfl_exchange(CFG, params, open_b, hp)
    want = np.asarray(sa(probs)).reshape(-1, probs.shape[-1])[list(PROBE)]
    assert got.shape == (len(PROBE), CFG.vocab) and got.dtype == np.float32
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=0)


def test_llm_dsfl_probe_refuses_the_edge_tree(task, stacked):
    """Through the two-level edge tree no whole client mean exists."""
    hp = LLMDsflHP(lr=5e-3, rounds=1, seed=0, open_batch=B, agg_edges=2)
    algo = LLMDSFLAlgorithm(CFG, hp, probe_rows=PROBE)
    with pytest.raises(ValueError, match="agg_edges"):
        FedEngine(algo).run(algo.init_from(stacked), task, rounds=1)


# ------------------------------------------------------- wire/comm parity ----
def test_llm_topk_measured_bytes_match_comm_model(task, stacked):
    """The LLM exchange's measured top-k bytes == CommModel.dsfl_topk_round
    with per-token payloads (|o_r| * S distribution uploads of k pairs)."""
    k = 8
    hp = LLMDsflHP(topk=k, rounds=1, open_batch=B)
    algo = LLMDSFLAlgorithm(CFG, hp)
    eng = FedEngine(algo, codec=wire.TopKCodec(k=k, n_classes=CFG.vocab))
    state = algo.init_from(stacked)
    cm = CommModel(K, CFG.vocab, 0, open_batch=B * S)
    assert eng.measured_round_bytes(state, task) == cm.dsfl_topk_round(k)


def test_llm_fp16_measured_bytes_match_comm_model(task, stacked):
    hp = LLMDsflHP(rounds=1, open_batch=B)
    algo = LLMDSFLAlgorithm(CFG, hp)
    eng = FedEngine(algo, codec=wire.FP16Codec())
    state = algo.init_from(stacked)
    cm = CommModel(K, CFG.vocab, 0, open_batch=B * S)
    assert eng.measured_round_bytes(state, task) == cm.dsfl_fp16_round()


# ------------------------------------------------------------ checkpointing --
def test_llm_engine_resume_without_start_round(task, stacked, tmp_path):
    """save -> load -> run continues the RNG stream automatically: the
    engine checkpoints rounds_done + history alongside the sharded state."""
    hp = LLMDsflHP(lr=5e-3, rounds=2, seed=0, open_batch=B)
    algo = LLMDSFLAlgorithm(CFG, hp)
    full = FedEngine(algo)
    out_full = full.run(algo.init_from(stacked), task)

    first = FedEngine(algo)
    mid = first.run(algo.init_from(stacked), task, rounds=1)
    path = os.path.join(tmp_path, "llm.msgpack")
    first.save_state(path, mid)

    second = FedEngine(algo)
    restored = second.load_state(path, algo.init_from(stacked))
    assert second.rounds_done == 1
    assert second.history == first.history
    out_resumed = second.run(restored, task, rounds=1)   # no start_round
    assert [h["round"] for h in second.history] == [1, 2]
    assert second.history == full.history
    for a, b in zip(jax.tree.leaves(out_full.clients.params),
                    jax.tree.leaves(out_resumed.clients.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
