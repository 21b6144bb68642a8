"""How the DS-FL client loops are lowered over the client axis.

`client.over_clients` runs a per-client function one client at a time
(`lax.map`) when one client's step holds a contraction of at least
`client.MAP_MIN_MACS` multiply-adds, and under `jax.vmap` otherwise.
Pins: at the paper's sizes the CNNs and the Reuters DNN map and tiny_mlp,
the IMDb LSTM and the small CNN of the paper-table scripts vmap; a mapped
CNN round equals the former vmapped round to f32 rounding (dense, masked
and participation-sparse) and lowers its update and distillation loops
with no grouped convolution; a round whose steps are small lowers to
exactly the former vmapped program; each client loop takes the path
`loop_path` names; the reference round of the parity tests
(`protocol.make_dsfl_round`) also matches the former vmap; and the size
count sees through nested jaxprs.

The models here are small, so the tests that need the map path lower the
threshold (`small_threshold`) between the test CNN's step (2.3e6
multiply-adds) and the test MLP's (1.6e5)."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import algorithms as alg
from repro.core import client, protocol
from repro.core.algorithms import BatchCtx, DSFLAlgorithm
from repro.core.client import (LocalSpec, _largest_contraction, local_update,
                               loop_path)
from repro.core.protocol import DSFLConfig
from repro.data.pipeline import build_image_task
from repro.models import smallnets as sn
from repro.models.smallnets import (apply_mnist_cnn, apply_tiny_mlp,
                                    init_mnist_cnn, init_tiny_mlp)
from repro.optim import optimizers as opt_lib

K = 4
HP = DSFLConfig(rounds=1, local_epochs=1, distill_epochs=1, batch_size=20,
                open_batch=40, aggregation="era")
PLANES = ["dense", "masked", "sparse"]


@pytest.fixture(scope="module")
def task():
    return build_image_task(seed=0, K=K, n_private=160, n_open=80,
                            n_test=40, distribution="non_iid")


def _init_cnn(k):
    return init_mnist_cnn(k, image_hw=16, widths=(8, 16), fc=32)


MODELS = {"cnn": (apply_mnist_cnn, _init_cnn),
          "mlp": (apply_tiny_mlp, init_tiny_mlp)}


def _setup(task, model, plane):
    apply_fn, init_fn = MODELS[model]
    algo = DSFLAlgorithm(apply_fn, HP)
    state = algo.init(jax.random.PRNGKey(0), init_fn, task)
    kw = {}
    if plane != "dense":
        kw["mask"] = jnp.array([1.0, 0.0, 1.0, 1.0])
    if plane == "sparse":
        kw["active_budget"] = 3
    ctx = BatchCtx(x=task.x_clients, y=task.y_clients, open_x=task.open_x,
                   o_idx=jnp.arange(HP.open_batch), **kw)
    return algo, state, ctx, jax.random.PRNGKey(1)


def _lower(algo, state, ctx, rng):
    # a fresh callable per call, so no trace is reused across a patch
    return jax.jit(lambda s, c, r: algo.round(s, c, r)).lower(state, ctx, rng)


@pytest.fixture
def small_threshold(monkeypatch):
    monkeypatch.setattr(client, "MAP_MIN_MACS", 1 << 20)


@pytest.fixture
def former_vmap(monkeypatch):
    """The client loops as they were: `jax.vmap` at every site."""
    def patch():
        monkeypatch.setattr(alg, "over_clients",
                            lambda fn, *args: jax.vmap(fn)(*args))
    return patch


@pytest.mark.usefixtures("small_threshold")
@pytest.mark.parametrize("plane", PLANES)
def test_mapped_cnn_round_matches_former_vmap(task, plane, former_vmap):
    algo, state, ctx, rng = _setup(task, "cnn", plane)
    mapped = _lower(algo, state, ctx, rng).compile()(state, ctx, rng)
    former_vmap()
    vmapped = _lower(algo, state, ctx, rng).compile()(state, ctx, rng)
    a, b = jax.tree.leaves(mapped), jax.tree.leaves(vmapped)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                   rtol=1e-5, atol=1e-6)
    # the round trained: the comparison is not between two untouched states
    assert any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(state.clients.params),
                               jax.tree.leaves(mapped[0].clients.params)))


def _conv_groups(text):
    """(feature_group_count, in the prediction step?) of every convolution
    of a lowered program (StableHLO with debug locations).  The prediction
    step's vmap is inlined into the round, so its convolutions carry its
    scope in their own location; the update and distillation loops' sit in
    scan bodies, on locations relative to the body."""
    locs = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))
    out = []
    for line in text.splitlines():
        if "stablehlo.convolution" not in line:
            continue
        groups = int(re.search(r"feature_group_count = (\d+)", line)[1])
        loc = re.search(r"loc\((#loc\d+)\)\s*$", line)[1]
        out.append((groups, alg.PREDICT in locs[loc]))
    return out


@pytest.mark.usefixtures("small_threshold")
@pytest.mark.parametrize("plane", ["dense", "sparse"])
def test_cnn_client_loops_lower_ungrouped(task, plane, former_vmap):
    algo, state, ctx, rng = _setup(task, "cnn", plane)
    loops = [g for g, pred in _conv_groups(
        _lower(algo, state, ctx, rng).as_text(debug_info=True)) if not pred]
    # update + client distillation + server distillation: 5 convs a step
    # (2 forward, 3 backward) in each of the three loops
    assert len(loops) >= 15
    assert max(loops) == 1
    # the former vmapped loops grouped them by client: the check bites
    former_vmap()
    former = [g for g, pred in _conv_groups(
        _lower(algo, state, ctx, rng).as_text(debug_info=True)) if not pred]
    lanes = 3 if plane == "sparse" else K
    assert max(former) == lanes


@pytest.mark.parametrize("plane", PLANES)
def test_matmul_round_lowering_unchanged(task, plane, former_vmap):
    algo, state, ctx, rng = _setup(task, "mlp", plane)
    text = _lower(algo, state, ctx, rng).as_text()
    former_vmap()
    assert _lower(algo, state, ctx, rng).as_text() == text


@pytest.mark.parametrize("plane", ["dense", "sparse"])
def test_small_cnn_round_lowering_unchanged(task, plane, former_vmap):
    """A convolution alone does not make a step map: the test CNN's is
    below the threshold, so its round is still the vmapped program."""
    algo, state, ctx, rng = _setup(task, "cnn", plane)
    text = _lower(algo, state, ctx, rng).as_text()
    former_vmap()
    assert _lower(algo, state, ctx, rng).as_text() == text


def _paper_step(model, batch, n, shape, dtype=jnp.float32):
    """`local_update` of one model and the abstract arrays of K=100
    clients: (fn, args) as `DSFLAlgorithm` hands them to `over_clients`."""
    small = {"cnn16": {"image_hw": 16, "widths": (8, 16), "fc": 32},
             "cnn16w": {"image_hw": 16}}
    net = (sn.make_smallnet("mnist_cnn", **small[model]) if model in small
           else sn.make_smallnet(model))
    spec = LocalSpec(net.apply, opt_lib.make("sgd", 0.1), 5, batch)
    w, s = jax.eval_shape(net.init, jax.random.PRNGKey(0))
    o = jax.eval_shape(spec.opt.init, w)
    w, s, o = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((100,) + a.shape, a.dtype), (w, s, o))
    x = jax.ShapeDtypeStruct((100, n) + shape, dtype)
    y = jax.ShapeDtypeStruct((100, n), jnp.int32)
    keys = jax.ShapeDtypeStruct((100, 2), jnp.uint32)
    return (lambda w, s, o, x, y, k: local_update(spec, w, s, o, x, y, k),
            (w, s, o, x, y, keys))


@pytest.mark.parametrize("model,batch,shape,dtype,path", [
    ("mnist_cnn", 100, (28, 28, 1), jnp.float32, "map"),
    ("fmnist_cnn", 100, (28, 28, 1), jnp.float32, "map"),
    ("reuters_dnn", 100, (10_000,), jnp.float32, "map"),
    ("tiny_mlp", 100, (16, 16, 1), jnp.float32, "vmap"),
    ("imdb_lstm", 100, (200,), jnp.int32, "vmap"),
    ("cnn16", 50, (16, 16, 1), jnp.float32, "vmap"),
    ("cnn16w", 50, (16, 16, 1), jnp.float32, "vmap"),
], ids=["mnist_cnn", "fmnist_cnn", "reuters_dnn", "tiny_mlp", "imdb_lstm",
        "cnn16", "cnn16w"])
def test_loop_path_by_step_size(model, batch, shape, dtype, path):
    """The paper's models at its batch sizes, K=100, traced abstractly;
    cnn16 is the paper-table scripts' CNN (`benchmarks/common.py`), cnn16w
    the paper's widths on 16-pixel images, whose step (9.2e7) ran as fast
    under either lowering on the chip."""
    fn, args = _paper_step(model, batch, 4 * batch, shape, dtype)
    assert loop_path(fn, *args) == path


@pytest.mark.usefixtures("small_threshold")
@pytest.mark.parametrize("model,plane,path", [
    ("cnn", "dense", "map"),
    ("cnn", "sparse", "map"),
    ("mlp", "dense", "vmap"),
    ("mlp", "masked", "vmap"),
])
def test_loop_path_at_each_client_loop(task, model, plane, path,
                                       monkeypatch):
    algo, state, ctx, rng = _setup(task, model, plane)
    taken = []

    def spy(fn, *args):
        taken.append((loop_path(fn, *args), jax.tree.leaves(args)[0].shape[0]))
        return jax.vmap(fn)(*args)

    monkeypatch.setattr(alg, "over_clients", spy)
    jax.eval_shape(algo.round, state, ctx, rng)
    lanes = 3 if plane == "sparse" else K
    assert taken == [(path, lanes)] * 2       # update, then distillation


@pytest.mark.usefixtures("small_threshold")
@pytest.mark.parametrize("model", ["cnn", "mlp"])
def test_reference_round_matches_former_vmap(task, model, monkeypatch):
    """`protocol.make_dsfl_round`, the golden reference of the engine
    parity tests, runs its client loops through `over_clients` too: pin it
    against the former vmapped computation on its own."""
    algo, state, _, rng = _setup(task, model, "dense")
    c, g = state.clients, state.server
    args = (c.params, c.model_state, c.opt_update, c.opt_distill, g.params,
            g.model_state, g.opt_distill, task.x_clients, task.y_clients,
            task.open_x, jnp.arange(HP.open_batch), rng)
    apply_fn = MODELS[model][0]
    new = jax.jit(protocol.make_dsfl_round(apply_fn, HP))(*args)
    monkeypatch.setattr(protocol, "over_clients",
                        lambda fn, *a: jax.vmap(fn)(*a))
    old = jax.jit(protocol.make_dsfl_round(apply_fn, HP))(*args)
    a, b = jax.tree.leaves(new), jax.tree.leaves(old)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        if model == "mlp":                    # still vmapped: bit for bit
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        else:
            np.testing.assert_allclose(np.asarray(x), np.asarray(y),
                                       rtol=1e-5, atol=1e-6)
    assert any(not np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(c.params),
                               jax.tree.leaves(new[0][0])))


def _conv(x, w):
    return jax.lax.conv_general_dilated(x, w, (1, 1), "VALID")


X = jax.ShapeDtypeStruct((2, 1, 6, 6), jnp.float32)
W = jax.ShapeDtypeStruct((3, 1, 3, 3), jnp.float32)


@pytest.mark.parametrize("fn,macs", [
    (lambda x, w: _conv(x, w), 864),
    (lambda x, w: jax.lax.scan(lambda c, _: (c, _conv(x, w)), 0.0,
                               None, length=2)[1], 864),
    (lambda x, w: jax.lax.cond(x.sum() > 0, lambda: _conv(x, w).sum(),
                               lambda: 0.0), 864),
    (lambda x, w: jax.jit(_conv)(x, w), 864),
    (lambda x, w: jax.grad(lambda v: _conv(x, v).sum())(w), 864),
    (lambda x, w: jax.lax.scan(
        lambda c, _: (c, x[..., :3, :3].reshape(2, 9) @ w.reshape(3, 9).T),
        0.0, None, length=2)[1], 54),
    (lambda x, w: jnp.einsum("bchw,ochw->bo", x[..., :3, :3], w), 54),
    (lambda x, w: (x * 2.0).sum() + w.sum(), 0),
], ids=["top", "scan", "cond", "jit", "grad", "scan-matmul", "einsum",
        "none"])
def test_largest_contraction_walks_nested_jaxprs(fn, macs):
    # conv: 2 x 3 x 4 x 4 outputs of 1 x 3 x 3 each (its weight gradient:
    # 27 outputs of 2 x 4 x 4); matmul: 2 x 3 outputs of 9
    assert _largest_contraction(jax.make_jaxpr(fn)(X, W).jaxpr) == macs
