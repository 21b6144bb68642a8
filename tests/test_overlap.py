"""The platform-tuning plane and the pipelined LLM exchange.

Covers `launch.platform` (preset registry, XLA_FLAGS merge semantics,
argparse wiring, provenance stamping, async-collective HLO detection) and
pins the LLM-scale pipelined round — `LLMDSFLAlgorithm.round_start` /
``round_finish`` through ``FedEngine.run(overlap=True)`` — bitwise against
the sequential schedule, plain and mesh-sharded.  The CI tier-1 job runs
this on 8 fake CPU devices (the ``cpu8`` tier), so the all-gather in the
exchange is a real multi-device collective there.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.engine import FedEngine
from repro.core.llm_algorithms import LLMDSFLAlgorithm
from repro.core.llm_dsfl import LLMDsflHP
from repro.data.pipeline import build_lm_task
from repro.launch import platform as pf
from repro.models.api import model_init
from repro.models.shardctx import axis_ctx

CFG = get_config("qwen1.5-4b").smoke()
K, B, S = 2, 4, 32


# ------------------------------------------------------------ presets -------
def test_preset_registry_names():
    assert {"default", "cpu8", "overlap", "overlap-cpu8", "x64"} <= set(
        pf.names())
    for name in pf.names():
        p = pf.PRESETS[name]
        assert p.name == name and p.description
        assert all(f.startswith("--xla_") and "=" in f for f in p.xla_flags)


def test_apply_unknown_preset_raises():
    with pytest.raises(ValueError, match="unknown platform preset"):
        pf.apply("definitely-not-a-preset")


@pytest.fixture
def clean_platform(monkeypatch):
    """Isolate preset application: scratch env, no backend-init warning,
    active-preset slot restored afterwards."""
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    monkeypatch.setattr(pf, "backend_initialized", lambda: False)
    monkeypatch.setattr(pf, "_active", pf._active)
    yield


def test_apply_merges_with_ambient_flags(clean_platform, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/tmp/d")
    pf.apply("overlap")
    flags = os.environ["XLA_FLAGS"].split()
    assert "--xla_dump_to=/tmp/d" in flags            # ambient survives
    for f in pf.PRESETS["overlap"].xla_flags:
        assert f in flags
    assert pf.active().name == "overlap"


def test_ambient_forced_device_count_wins(clean_platform, monkeypatch):
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=2")
    pf.apply("overlap-cpu8")
    flags = os.environ["XLA_FLAGS"].split()
    # the preset must NOT add a second (conflicting) forced count
    forced = [f for f in flags
              if f.startswith("--xla_force_host_platform_device_count")]
    assert forced == ["--xla_force_host_platform_device_count=2"]
    for f in pf.PRESETS["overlap-cpu8"].xla_flags:
        assert f in flags


def test_apply_without_ambient_sets_device_count(clean_platform):
    pf.apply("cpu8")
    assert ("--xla_force_host_platform_device_count=8"
            in os.environ["XLA_FLAGS"].split())


def test_apply_is_idempotent(clean_platform):
    pf.apply("overlap")
    once = os.environ["XLA_FLAGS"]
    pf.apply("overlap")
    assert os.environ["XLA_FLAGS"] == once


def _child(code: str, **env) -> str:
    """Run ``code`` in a fresh CPU python (XLA_FLAGS are read once, at
    backend init, so a flag set can only be tried in a new process)."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    full = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
                JAX_PLATFORMS="cpu")
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full.update(env)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=full, timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    return r.stdout.strip()


def test_overlap_flags_start_a_cpu_backend():
    """Every flag of the ``overlap`` preset is known to the installed
    jaxlib: an unknown one aborts backend init (parse_flags_from_env)."""
    flags = " ".join(pf.PRESETS["overlap"].xla_flags)
    out = _child("import jax, jax.numpy as jnp; "
                 "print(float(jnp.ones(3).sum()), jax.devices()[0].platform)",
                 XLA_FLAGS=flags)
    assert out.splitlines()[-1] == "3.0 cpu"


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_lands_where_asked(tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX; without it the
    cache is the checkout's fixed ``.jax_cache/``."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    out = _child("import jax; from repro.launch import platform as pf; "
                 "d = pf.enable_compile_cache(); "
                 "print(d); print(jax.config.jax_compilation_cache_dir)",
                 **env)
    returned, configured = out.splitlines()[-2:]
    want = (str(tmp_path / env_dir) if env_dir is not None
            else os.path.join(root, ".jax_cache"))
    assert returned == configured == want


def test_apply_after_backend_init_warns(clean_platform, monkeypatch):
    monkeypatch.setattr(pf, "backend_initialized", lambda: True)
    with pytest.warns(UserWarning, match="after jax backend init"):
        pf.apply("overlap")


def test_from_args_roundtrip(clean_platform):
    import argparse
    ap = argparse.ArgumentParser()
    pf.add_args(ap)
    assert pf.from_args(ap.parse_args([])) is None
    got = pf.from_args(ap.parse_args(["--platform-preset", "cpu8"]))
    assert got is pf.PRESETS["cpu8"]


def test_provenance_stamps_preset(clean_platform):
    from repro.obs.provenance import RunProvenance
    pf.apply("overlap")
    prov = RunProvenance.collect()
    assert prov.platform_preset == "overlap"
    assert "--xla_gpu_enable_latency_hiding_scheduler=true" in prov.xla_flags


def test_async_collectives_in_markers():
    assert pf.async_collectives_in(
        "%ag-start = all-gather-start(f32[8] %x), replica_groups={}")
    assert pf.async_collectives_in("... all-reduce-start ...")
    assert not pf.async_collectives_in(
        "%ag = all-gather(f32[8] %x)")       # sync lowering: no overlap
    assert not pf.async_collectives_in("")


# ------------------------------------------------ LLM pipelined parity ------
@pytest.fixture(scope="module")
def task():
    return build_lm_task(seed=0, K=K, batch=B, seq=S, vocab=CFG.vocab)


@pytest.fixture(scope="module")
def stacked(rng):
    return jax.vmap(lambda k: model_init(CFG, k))(jax.random.split(rng, K))


def _states_equal(a, b):
    for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


def _llm_run(task, stacked, topk, overlap, mesh=None, rounds=4, chunk=2):
    hp = LLMDsflHP(lr=5e-3, rounds=rounds, seed=0, open_batch=B, topk=topk)
    algo = LLMDSFLAlgorithm(CFG, hp)
    eng = FedEngine(algo, mesh=mesh)
    state = eng.run(algo.init_from(stacked), task, rounds=rounds,
                    chunk_rounds=chunk, overlap=overlap)
    return eng, state


@pytest.mark.parametrize("topk", [None, 8])
def test_llm_overlap_bitwise_identical_to_sequential(task, stacked, topk):
    """The LLM tentpole pin, dense and through the top-k wire codec: the
    pipelined exchange (the round's only cross-pod collective issued a
    round early) changes no bits."""
    e1, s1 = _llm_run(task, stacked, topk, overlap=False)
    e2, s2 = _llm_run(task, stacked, topk, overlap=True)
    _states_equal(s1, s2)
    assert e1.history == e2.history


def test_llm_overlap_parity_under_mesh(task, stacked):
    """Same pin on the mesh-sharded engine path (in_shardings jit): on the
    8-fake-device CI tier the exchange all-gather is a real collective."""
    from repro.launch.mesh import make_client_mesh
    mesh = make_client_mesh(K)
    with axis_ctx(mesh, batch_axes=("data",)):
        e1, s1 = _llm_run(task, stacked, 8, overlap=False, mesh=mesh)
        e2, s2 = _llm_run(task, stacked, 8, overlap=True, mesh=mesh)
    _states_equal(s1, s2)
    assert e1.history == e2.history


def test_overlap_telemetry_is_host_side_and_published(tmp_path):
    """Satellite pin: the pipelined path emits the same `engine.chunk`
    spans as the sequential one — one per chunk, at chunk boundaries,
    never inside the compiled chunk — and instrumentation must not change
    a bit of the history or the state."""
    import json

    from repro import obs
    from repro.core.algorithms import DSFLAlgorithm
    from repro.core.protocol import DSFLConfig
    from repro.data.pipeline import build_image_task
    from repro.models.smallnets import apply_tiny_mlp, init_tiny_mlp

    hp = DSFLConfig(rounds=4, local_epochs=1, distill_epochs=1,
                    batch_size=20, open_batch=40, aggregation="era")
    itask = build_image_task(seed=0, K=4, n_private=160, n_open=80,
                             n_test=40, distribution="non_iid")

    def go(overlap):
        eng = FedEngine(DSFLAlgorithm(apply_tiny_mlp, hp))
        state = eng.run(eng.init(init_tiny_mlp, itask), itask, rounds=4,
                        chunk_rounds=2, overlap=overlap)
        return eng.history, state

    plain, s_plain = go(overlap=True)
    chunks = {}
    for overlap in (False, True):
        path = os.path.join(tmp_path, f"overlap{int(overlap)}.jsonl")
        with obs.trace_to(str(path)):
            prev = obs.install_registry(obs.MetricsRegistry())
            try:
                traced, s_traced = go(overlap)
            finally:
                obs.install_registry(prev)
        recs = [json.loads(l) for l in open(path) if l.strip()]
        chunks[overlap] = [r["args"] for r in recs
                           if r.get("name") == "engine.chunk"]
        assert traced == plain                 # host-side only: same bits
        _states_equal(s_traced, s_plain)
    assert len(chunks[True]) == len(chunks[False]) == 2
    assert [c["overlap"] for c in chunks[True]] == [True, True]
    assert [c["start_round"] for c in chunks[True]] == [0, 2]


def test_llm_round_equals_finish_of_start(task, stacked):
    """The split identity the pipeline is built on:
    round == round_finish(state, ctx, round_start(state, ctx, rng), rng)."""
    from repro.core.algorithms import BatchCtx, EMPTY
    hp = LLMDsflHP(lr=5e-3, rounds=1, seed=0, open_batch=B, topk=8)
    algo = LLMDSFLAlgorithm(CFG, hp)
    state = algo.init_from(stacked)
    o_idx = jnp.arange(B)
    ctx = BatchCtx(x=task.x_clients, open_x=task.open_x, o_idx=o_idx,
                   mask=EMPTY, stale=EMPTY, active_budget=None)
    rng = jax.random.PRNGKey(0)
    s_ref, m_ref = jax.jit(algo.round)(state, ctx, rng)
    split = jax.jit(lambda s, c, r: algo.round_finish(
        s, c, algo.round_start(s, c, r), r))
    s_got, m_got = split(state, ctx, rng)
    _states_equal(s_ref, s_got)
    _states_equal(m_ref, m_got)
