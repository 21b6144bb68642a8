"""`smallnets.maxpool2` against ``lax.reduce_window``, bit for bit.

`maxpool2` keeps ``reduce_window``'s forward and replaces its gradient
(XLA's ``select_and_scatter``, which the TPU runs as an unfused pass over
the pool's input) with element-wise selects, `maxpool2_grad`.  Pins: the
output and the input cotangent equal ``reduce_window``'s bit for bit under
``jax.jit``, ties, ``-inf``, NaN, signed zeros, odd sizes, bf16 and a vmap
over clients included; the gradient of an MNIST CNN client step lowers with
no ``select_and_scatter`` (and did with the plain pool); and that step
still runs one client at a time (`client.loop_path`)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.client import LocalSpec, local_update, loop_path
from repro.core.losses import xent_int_labels
from repro.models import smallnets as sn
from repro.optim import optimizers as opt_lib


def reference(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _draw(case, shape, dtype, rng):
    x = rng.standard_normal(shape)
    if case == "ties":           # a constant background, as synthetic digits
        x[:, : shape[1] // 2] = 0.75
    elif case == "relu_zeros":   # whole windows at 0 after a ReLU
        x = np.maximum(x - 1.0, 0.0)
    elif case == "neg_inf":      # some entries and some whole windows
        x[rng.random(shape) < 0.3] = -np.inf
        x[:, :2, :2] = -np.inf
    elif case == "nan":
        x[rng.random(shape) < 0.1] = np.nan
    elif case == "signed_zeros":
        x = np.where(rng.random(shape) < 0.5, -0.0, 0.0)
    return jnp.asarray(x, dtype)


def _bits(a):
    a = np.asarray(a)
    return a.view({2: np.uint16, 4: np.uint32}[a.dtype.itemsize])


CASES = ["random", "ties", "relu_zeros", "neg_inf", "nan", "signed_zeros"]
SHAPES = {"even": (4, 24, 24, 32), "odd": (3, 25, 25, 8)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", ["even", "odd", "vmap"])
@pytest.mark.parametrize("case", CASES)
def test_maxpool2_bitwise_equals_reduce_window(case, shape, dtype):
    rng = np.random.default_rng(CASES.index(case))
    lead = (2,) if shape == "vmap" else ()          # a client axis
    dims = SHAPES["odd" if shape == "vmap" else shape]
    x = _draw(case, lead + dims, dtype, rng)

    def fwd_and_vjp(pool):
        def one(x, g):
            y, back = jax.vjp(pool, x)
            return y, back(g)[0]
        return jax.jit(jax.vmap(one) if lead else one)

    y = jax.eval_shape(jax.vmap(reference) if lead else reference, x)
    g = jnp.asarray(rng.standard_normal(y.shape), dtype)
    want_y, want_dx = fwd_and_vjp(reference)(x, g)
    got_y, got_dx = fwd_and_vjp(sn.maxpool2)(x, g)
    assert got_y.dtype == want_y.dtype and got_dx.dtype == want_dx.dtype
    np.testing.assert_array_equal(_bits(got_y), _bits(want_y))
    np.testing.assert_array_equal(_bits(got_dx), _bits(want_dx))
    # each window sends its cotangent to one element, none to dropped ones
    if shape != "even":
        assert not np.asarray(got_dx)[..., 24:, :, :].any()
        assert not np.asarray(got_dx)[..., 24:, :].any()


def _client_step(batch=100):
    """The gradient of one MNIST CNN client step's cross-entropy."""
    w, s = jax.eval_shape(sn.init_mnist_cnn, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((batch, 28, 28, 1), jnp.float32)
    y = jax.ShapeDtypeStruct((batch,), jnp.int32)

    def step(w, s, x, y):
        def loss(w):
            logits, _ = sn.apply_mnist_cnn(w, s, x, True)
            return xent_int_labels(logits, y)
        return jax.grad(loss)(w)
    return step, (w, s, x, y)


def test_client_step_gradient_lowers_without_select_and_scatter(
        monkeypatch):
    step, args = _client_step()
    assert "select_and_scatter" not in jax.jit(step).lower(*args).as_text()
    # the plain pool's gradient holds one per pool: the check bites (a
    # fresh step, so no trace is reused across the patch)
    monkeypatch.setattr(sn, "maxpool2", reference)
    step, args = _client_step()
    assert jax.jit(step).lower(*args).as_text().count(
        "stablehlo.select_and_scatter") == 2


def test_client_step_still_maps():
    """The pool holds no contraction, so the paper's MNIST step still runs
    one client at a time at K=100."""
    w, s = jax.eval_shape(sn.init_mnist_cnn, jax.random.PRNGKey(0))
    spec = LocalSpec(sn.apply_mnist_cnn, opt_lib.make("sgd", 0.1), 5, 100)
    o = jax.eval_shape(spec.opt.init, w)
    w, s, o = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((100,) + a.shape, a.dtype), (w, s, o))
    x = jax.ShapeDtypeStruct((100, 400, 28, 28, 1), jnp.float32)
    y = jax.ShapeDtypeStruct((100, 400), jnp.int32)
    keys = jax.ShapeDtypeStruct((100, 2), jnp.uint32)
    assert loop_path(lambda *a: local_update(spec, *a),
                     w, s, o, x, y, keys) == "map"
