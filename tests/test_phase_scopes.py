"""The DS-FL round's steps as named scopes, and `repro.obs` spans on the
profiler's clock.

Pins: each step of the dense and participation-sparse DS-FL round (and of
the LLM round) carries its `jax.named_scope` in the lowered program, the
test-set eval carries ``fed_eval``; ``obs.annotate`` opens a
`jax.profiler.TraceAnnotation` per span and leaves the disabled path the
shared no-op; and a profiled run with annotation on is bitwise the plain
run (scopes and annotations are metadata and host marks only)."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core import algorithms as alg
from repro.core.algorithms import BatchCtx, DSFLAlgorithm
from repro.core.engine import FedEngine, make_eval_fn
from repro.core.protocol import EVAL, DSFLConfig
from repro.data.pipeline import build_image_task
from repro.models.smallnets import apply_tiny_mlp, init_tiny_mlp
from repro.obs import trace as obs_trace

K = 4
HP = DSFLConfig(rounds=2, local_epochs=1, distill_epochs=1, batch_size=20,
                open_batch=40, aggregation="era")
STEPS = (alg.UPDATE, alg.PREDICT, alg.AGGREGATE, alg.DISTILL,
         alg.SERVER_DISTILL)


@pytest.fixture(scope="module")
def task():
    return build_image_task(seed=0, K=K, n_private=160, n_open=80,
                            n_test=40, distribution="non_iid")


@pytest.fixture
def annotation_off():
    yield
    obs.annotate(False)
    assert obs_trace._MAKE_SPAN is None, "a test leaked annotation"


def _lowered_round(task, **ctx_kw):
    algo = DSFLAlgorithm(apply_tiny_mlp, HP)
    state = algo.init(jax.random.PRNGKey(0), init_tiny_mlp, task)
    ctx = BatchCtx(x=task.x_clients, y=task.y_clients, open_x=task.open_x,
                   o_idx=jnp.arange(HP.open_batch), **ctx_kw)
    return jax.jit(algo.round).lower(state, ctx, jax.random.PRNGKey(1)
                                     ).as_text(debug_info=True)


@pytest.mark.parametrize("plane", ["dense", "masked", "sparse"])
def test_round_program_names_every_step(task, plane):
    kw = {}
    if plane != "dense":
        kw["mask"] = jnp.array([1.0, 0.0, 1.0, 0.0])
    if plane == "sparse":
        kw["active_budget"] = 2
    text = _lowered_round(task, **kw)
    for name in STEPS:
        assert f"/{name}/" in text, f"{name} missing from the {plane} round"


def test_eval_program_names_fed_eval(task, monkeypatch):
    algo = DSFLAlgorithm(apply_tiny_mlp, HP)
    state = algo.init(jax.random.PRNGKey(0), init_tiny_mlp, task)
    w, s = algo.eval_params(state)
    captured = {}
    real_jit = jax.jit

    def spy(fn, *a, **k):          # make_eval_fn jits its test-set forward
        captured["fn"] = fn
        return real_jit(fn, *a, **k)

    monkeypatch.setattr(jax, "jit", spy)
    make_eval_fn(apply_tiny_mlp, task.x_test, task.y_test)
    monkeypatch.undo()
    text = jax.jit(captured["fn"]).lower(w, s).as_text(debug_info=True)
    assert f"/{EVAL}/" in text


def test_llm_round_program_names_its_steps():
    from repro.configs import get_config
    from repro.core.llm_dsfl import LLMDsflHP, dsfl_round_step
    from repro.data.pipeline import build_lm_task
    from repro.models.api import model_init
    cfg = get_config("qwen1.5-4b").smoke()
    lm = build_lm_task(seed=0, K=2, batch=2, seq=16, vocab=cfg.vocab)
    stacked = jax.vmap(lambda k: model_init(cfg, k))(
        jax.random.split(jax.random.PRNGKey(0), 2))
    open_batch = jax.tree.map(lambda v: v[:2], lm.open_x)
    for topk in (None, 4):
        hp = LLMDsflHP(topk=topk)
        text = jax.jit(lambda p, b, o: dsfl_round_step(cfg, p, b, o, hp)
                       ).lower(stacked, lm.x_clients, open_batch
                               ).as_text(debug_info=True)
        # the hybrid CE + KD step: the update and distillation scopes
        # reach the backward pass through the gradient's transforms
        for name in (alg.UPDATE, alg.PREDICT, alg.AGGREGATE, alg.DISTILL):
            assert name in text, (topk, name)
        assert f"transpose(jvp({alg.DISTILL}))" in text
        assert alg.SERVER_DISTILL not in text     # no server model


# --------------------------------------------------------------- annotate ---
class _Recorder:
    """Stands in for `jax.profiler.TraceAnnotation`: records enter/exit."""
    log: list = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Recorder.log.append(("enter", self.name))
        return self

    def __exit__(self, *exc):
        _Recorder.log.append(("exit", self.name))
        return False


def test_annotate_off_is_the_shared_null_span(annotation_off):
    assert obs.annotate(False) is False
    assert obs.span("engine.round", "engine") is obs_trace._NULL_SPAN


def test_annotate_on_opens_a_trace_annotation(annotation_off, monkeypatch,
                                              tmp_path):
    import jax.profiler
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.log = []
    assert obs.annotate(True) is False
    with obs.span("engine.sample", "engine", round=3) as sp:
        sp.set(anything=1)          # chainable without a tracer
    assert _Recorder.log == [("enter", "engine.sample"),
                             ("exit", "engine.sample")]
    # with a tracer installed as well: both the annotation and the JSONL
    path = tmp_path / "t.jsonl"
    _Recorder.log = []
    with obs.trace_to(str(path)):
        with obs.span("engine.sync", "engine") as sp:
            sp.set(n=2)
    assert _Recorder.log == [("enter", "engine.sync"),
                             ("exit", "engine.sync")]
    recs = [json.loads(l) for l in open(path) if l.strip()]
    (rec,) = [r for r in recs if r.get("name") == "engine.sync"]
    assert rec["type"] == "span" and rec["args"] == {"n": 2}
    assert obs.annotate(False) is True
    assert obs.span("x") is obs_trace._NULL_SPAN


def _leaves(tree):
    return [np.asarray(l) for l in jax.tree.leaves(tree)]


def test_profiled_annotated_run_is_bitwise_the_plain_run(task, tmp_path,
                                                         annotation_off):
    """One tiny round through the engine's loop (with its eval), plain and
    under `jax.profiler.trace` with annotation on: same bits, and the
    program's spans are on the profile's host plane."""
    def go():
        eng = FedEngine(DSFLAlgorithm(apply_tiny_mlp, HP),
                        make_eval_fn(apply_tiny_mlp, task.x_test,
                                     task.y_test))
        state = eng.run(eng.init(init_tiny_mlp, task), task, rounds=1)
        jax.block_until_ready(state)
        return state, eng.history

    plain, hist = go()
    obs.annotate(True)
    with jax.profiler.trace(str(tmp_path)):
        traced, hist_t = go()
    obs.annotate(False)
    for a, b in zip(_leaves(plain), _leaves(traced)):
        np.testing.assert_array_equal(a, b)
    assert hist_t == hist

    from jax.profiler import ProfileData
    (path,) = list(tmp_path.glob("**/*.xplane.pb"))
    names = {ev.name for plane in ProfileData.from_file(str(path)).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    for want in ("engine.round", "engine.sample", "engine.sync",
                 "engine.eval"):
        assert want in names, want
