"""Pod-scale DS-FL round step: convergence, FedAvg equivalence, top-k path,
microbatch-accumulation equivalence, attack surface, and the token-sharded
exchange on four virtual devices against the unsharded round."""
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
import pytest

from repro.core.llm_dsfl import (LLMDsflHP, dsfl_client_step, dsfl_round_step,
                                 fedavg_round_step, predict_open_probs)
from repro.data.pipeline import lm_open_batch, lm_private_batches
from repro.models.api import model_init

CFG = get_config("qwen1.5-4b").smoke()
K = 2


def make_setup(rng, batch=4, seq=32):
    stacked = jax.vmap(lambda k: model_init(CFG, k))(jax.random.split(rng, K))
    private = lm_private_batches(jax.random.fold_in(rng, 1), K, batch, seq,
                                 CFG.vocab)
    open_b = lm_open_batch(jax.random.fold_in(rng, 2), batch, seq, CFG.vocab)
    return stacked, private, open_b


def test_dsfl_round_reduces_loss(rng):
    stacked, private, open_b = make_setup(rng)
    hp = LLMDsflHP(lr=5e-3)
    step = jax.jit(lambda p, pb, ob: dsfl_round_step(CFG, p, pb, ob, hp))
    losses = []
    params = stacked
    for _ in range(8):
        params, loss = step(params, private, open_b)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_dsfl_round_topk_path_runs(rng):
    stacked, private, open_b = make_setup(rng)
    hp = LLMDsflHP(lr=5e-3, topk=8)
    params, loss = jax.jit(
        lambda p, pb, ob: dsfl_round_step(CFG, p, pb, ob, hp))(
        stacked, private, open_b)
    assert bool(jnp.isfinite(loss))


def test_fedavg_round_syncs_clients(rng):
    stacked, private, _ = make_setup(rng)
    new, loss = jax.jit(
        lambda p, pb: fedavg_round_step(CFG, p, pb, 1e-3))(stacked, private)
    for leaf in jax.tree.leaves(new):
        np.testing.assert_allclose(leaf[0], leaf[1], atol=1e-6)


def test_microbatch_accumulation_matches_full_batch(rng):
    params = model_init(CFG, rng)
    private = lm_open_batch(jax.random.fold_in(rng, 1), 4, 32, CFG.vocab)
    open_b = lm_open_batch(jax.random.fold_in(rng, 2), 4, 32, CFG.vocab)
    teacher = jax.nn.softmax(
        jax.random.normal(rng, (4, 32, CFG.vocab)), -1).astype(jnp.bfloat16)
    hp1 = LLMDsflHP(lr=1e-2, microbatches=1)
    hp2 = LLMDsflHP(lr=1e-2, microbatches=2)
    p1, l1 = dsfl_client_step(CFG, params, private, open_b, teacher, hp1)
    p2, l2 = dsfl_client_step(CFG, params, private, open_b, teacher, hp2)
    # CE means over microbatches == mean over full batch (equal sizes)
    assert abs(float(l1) - float(l2)) < 5e-2
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=5e-2, rtol=1e-2)


def test_sparse_round_bitwise_matches_dense_weights_path(rng):
    """Participation-sparse pod rounds: ``active_budget=1`` on a 2-pod
    fleet (one absent client) computes half the client stack and is
    bitwise identical to the dense ``weights=`` round — for DS-FL and for
    the FedAvg benchmark twin."""
    stacked, private, open_b = make_setup(rng)
    hp = LLMDsflHP(lr=5e-3)
    mask = jnp.asarray([1.0, 0.0])
    w = mask * 0.7

    d = jax.jit(lambda p, pb, ob: dsfl_round_step(
        CFG, p, pb, ob, hp, weights=w, mask=mask))(stacked, private, open_b)
    s = jax.jit(lambda p, pb, ob: dsfl_round_step(
        CFG, p, pb, ob, hp, weights=w, mask=mask, active_budget=1))(
        stacked, private, open_b)
    for a, b in zip(jax.tree.leaves(d), jax.tree.leaves(s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    d = jax.jit(lambda p, pb: fedavg_round_step(
        CFG, p, pb, 1e-3, weights=w, mask=mask))(stacked, private)
    s = jax.jit(lambda p, pb: fedavg_round_step(
        CFG, p, pb, 1e-3, weights=w, mask=mask, active_budget=1))(
        stacked, private)
    for a, b in zip(jax.tree.leaves(d), jax.tree.leaves(s)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_predict_open_probs_is_distribution(rng):
    params = model_init(CFG, rng)
    open_b = lm_open_batch(rng, 2, 16, CFG.vocab)
    probs = predict_open_probs(CFG, params, open_b)
    np.testing.assert_allclose(np.sum(np.asarray(probs, np.float32), -1),
                               1.0, atol=2e-2)


def test_poisoned_logits_are_diluted_by_era(rng):
    """DS-FL's attack surface: one malicious client's adversarial logits get
    averaged away (Table 4 mechanism) — the aggregated teacher stays closer
    to the benign mean than to the attacker's distribution."""
    from repro.core.aggregation import era
    kb, km = jax.random.split(rng)
    # benign clients share a consensus signal (they model the same task)
    consensus = jax.random.normal(km, (1, 32, 16)) * 2.0
    benign = jax.nn.softmax(consensus
                            + jax.random.normal(kb, (7, 32, 16)), -1)
    target = jax.nn.one_hot(jnp.zeros((32,), jnp.int32), 16)[None]
    probs = jnp.concatenate([benign, target], axis=0)
    g = era(probs, 0.1)
    benign_mean = jnp.mean(benign, 0)
    attacker_mass = float(jnp.mean(g[:, 0]))
    benign_top = float(jnp.mean(jnp.max(benign_mean, -1)))
    agree = np.mean(np.argmax(np.asarray(g), -1)
                    == np.argmax(np.asarray(benign_mean), -1))
    assert agree > 0.8


# --------------------------------------------- token-sharded exchange -------
# One child process on four virtual CPU devices (the device count is read
# once, at backend init) computes every reading; the tests below check them.
POD, B4, S4 = 4, 4, 32                  # B4 * S4 = 128 tokens, 32 a pod
PROBE = (0, 5, 17, 33, 64, 90, 100, 127)  # every pod owns some rows
COLLECTIVE = re.compile(r"^\s*%\S+ = (.*?) (all-reduce|all-gather|all-to-all|"
                        r"reduce-scatter)(?:-start)?\(")
ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\]")


def _collectives(hlo: str):
    """(opcode, dtype, elements) of each array a collective returns."""
    out = []
    for line in hlo.splitlines():
        m = COLLECTIVE.match(line)
        if m:
            for dt, dims in ARRAY.findall(m.group(1)):
                out.append((m.group(2), dt,
                            int(np.prod([int(d) for d in dims.split(",")
                                         if d]))))
    return out


def _bits(x):
    a = np.asarray(x)
    return a.view(np.uint16) if a.dtype.itemsize == 2 else a.view(np.uint32)


def _pod4_readings() -> dict:
    """Runs in the child: the sharded exchange and round against the
    single-device ones, with the exchange counter of each."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro import obs
    from repro.core.engine import FedEngine
    from repro.core.llm_algorithms import LLMDSFLAlgorithm
    from repro.core.llm_dsfl import _aggregate
    from repro.data.pipeline import build_lm_task
    from repro.launch.mesh import make_client_mesh
    from repro.models.shardctx import axis_ctx
    from repro.obs.metrics import MetricsRegistry

    reg = MetricsRegistry()
    obs.install_registry(reg)

    def counted(fn):
        before = dict(reg.snapshot())
        res = fn()
        now = reg.snapshot()
        return res, sorted(k.rsplit(".", 1)[-1] for k in now
                           if k.startswith("dsfl.exchange.")
                           and now[k] != before.get(k, 0))

    mesh = make_client_mesh(POD)
    one = make_client_mesh(POD, devices=jax.devices()[:1])
    rng = jax.random.PRNGKey(0)
    out = {}

    def aggregate_case(name, shape, hp, m):
        probs = jax.nn.softmax(3.0 * jax.random.normal(rng, shape), -1
                               ).astype(jnp.bfloat16)
        rows = tuple(range(int(np.prod(shape[1:-1]))))
        f = jax.jit(lambda p: _aggregate(p, hp, None, rows))
        want = f(probs)
        with axis_ctx(m):
            sharded = jax.device_put(probs, NamedSharding(m, P("pod")))
            f = jax.jit(lambda p: _aggregate(p, hp, None, rows))
            (got, path) = counted(lambda: f(sharded))
            hlo = f.lower(sharded).compile().as_text()
        out[name] = {
            "teacher_equal": bool(np.array_equal(_bits(want[0]),
                                                 _bits(got[0]))),
            "mean_equal": bool(np.array_equal(_bits(want[1]),
                                              _bits(got[1]))),
            "path": path, "collectives": _collectives(hlo),
            "block": int(np.prod(shape[1:]))}

    V = CFG.vocab
    aggregate_case("era", (POD, B4, S4, V), LLMDsflHP(), mesh)
    aggregate_case("sa", (POD, B4, S4, V), LLMDsflHP(aggregation="sa"), mesh)
    aggregate_case("indivisible", (POD, 3, 6, V), LLMDsflHP(), mesh)
    aggregate_case("one_device", (POD, B4, S4, V), LLMDsflHP(), one)

    # one engine round with the probe, against the unsharded round step
    task = build_lm_task(seed=0, K=POD, batch=B4, seq=S4, vocab=V)
    stacked = jax.vmap(lambda k: model_init(CFG, k))(
        jax.random.split(rng, POD))
    hp = LLMDsflHP(lr=5e-3, rounds=1, seed=0, open_batch=B4)
    algo = LLMDSFLAlgorithm(CFG, hp, probe_rows=PROBE)
    eng = FedEngine(algo, mesh=mesh)
    state = algo.init_from(stacked)
    with axis_ctx(mesh, batch_axes=("data",)):
        res, path = counted(lambda: eng.run(state, task, rounds=1))
        hlo = eng.compiled_hlo(state, task)
    _, _, ri = jax.random.split(jax.random.PRNGKey(hp.seed), 3)
    o_idx = jax.random.choice(ri, B4, (B4,), replace=False)
    ref, _, ref_mean = jax.jit(lambda p, pb, ox, oi: dsfl_round_step(
        CFG, p, pb, jax.tree.map(lambda a: jnp.take(a, oi, axis=0), ox), hp,
        probe_rows=PROBE))(stacked, task.x_clients, task.open_x, o_idx)
    close = all(np.allclose(np.asarray(a, np.float32),
                            np.asarray(b, np.float32), atol=5e-2, rtol=1e-2)
                for a, b in zip(jax.tree.leaves(res.clients.params),
                                jax.tree.leaves(ref)))
    out["round"] = {
        "probe_equal": bool(np.array_equal(
            _bits(eng.last_metrics["client_mean"]), _bits(ref_mean))),
        "params_close": close, "path": path,
        "collectives": [c for h in hlo for c in _collectives(h)],
        "block": B4 * S4 * V}
    print(json.dumps(out))


@pytest.fixture(scope="module")
def pod4():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(os.path.dirname(here), "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    code = (f"import sys; sys.path.insert(0, {here!r}); "
            "import test_llm_dsfl as t; t._pod4_readings()")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("agg", ["era", "sa"])
def test_token_sharded_aggregate_is_bitwise_the_unsharded_one(pod4, agg):
    """On pod-sharded uploads the exchange gives the single-device teacher
    and client mean bit for bit, and counts the token-sharded path."""
    r = pod4[agg]
    assert r["teacher_equal"] and r["mean_equal"]
    assert r["path"] == ["token_sharded"]


def test_token_sharded_round_matches_unsharded_round(pod4):
    """One engine round on a 4-pod mesh reports the unsharded round's probed
    client mean bitwise; its parameters stay within the sharded-engine
    tolerance of `test_llm_dsfl_sharded_engine_round_runs`."""
    r = pod4["round"]
    assert r["probe_equal"] and r["params_close"]
    assert r["path"] == ["token_sharded"]


def test_token_sharded_round_has_no_full_f32_collective(pod4):
    """No collective of the compiled round carries float32 at a pod's share
    of the (B, S, V) block or more: the f32 all-reduce is gone."""
    r = pod4["round"]
    big = [c for c in r["collectives"]
           if c[1] == "f32" and c[2] >= r["block"] // POD]
    assert not big, big


def test_token_sharded_exchange_moves_2_byte_elements(pod4):
    """The exchange is an all-to-all of the uploads and an all-gather of the
    teacher, both of 2-byte elements."""
    ops = {(op, dt) for op, dt, _ in pod4["round"]["collectives"]
           if op in ("all-to-all", "all-gather")}
    assert {op for op, _ in ops} == {"all-to-all", "all-gather"}
    assert {dt for _, dt in ops} <= {"u16", "bf16"}, ops


@pytest.mark.parametrize("case", ["indivisible", "one_device"])
def test_exchange_falls_back_to_the_replicated_mean(pod4, case):
    """Where the pods do not divide the tokens (18 on 4 pods), or the mesh
    has one device, the round keeps the replicated program: the f32 mean
    of the whole block (all-reduced across pods where there are several),
    no all-to-all, the single-device result, and the counter says so."""
    r = pod4[case]
    assert r["path"] == ["replicated"]
    assert r["teacher_equal"] and r["mean_equal"]
    ops = {op for op, _, _ in r["collectives"]}
    assert "all-to-all" not in ops
    if case == "indivisible":
        assert ("all-reduce", "f32", r["block"]) in {
            tuple(c) for c in r["collectives"]}
    else:
        assert not ops
