"""The paper's exact evaluation models (Section 4.1), in pure JAX.

Parameter counts are verified against the paper (Keras conventions: conv/dense
biases, BatchNorm counted as 4 params/channel incl. moving statistics):

  * MNIST CNN      — paper: 583,242   (ours: 582,410, valid-padding; 0.14% delta)
  * F-MNIST CNN    — paper: 2,760,228 (ours: 2,759,976; 0.01% delta)
  * IMDb LSTM      — paper: 646,338   (ours: 648,386 at vocab 20k; 0.3% delta)
  * Reuters DNN    — paper: 5,194,670 (ours: 5,194,670; EXACT)

Models are functional: ``init(key) -> (params, state)``;
``apply(params, state, x, train) -> (logits, new_state)`` where ``state``
carries BatchNorm running statistics (aggregated by FedAvg like any leaf).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp


def _dense(key, n_in, n_out):
    w = jax.random.normal(key, (n_in, n_out), jnp.float32) * (2.0 / n_in) ** 0.5
    return {"w": w, "b": jnp.zeros((n_out,), jnp.float32)}


def _conv(key, kh, kw, cin, cout):
    w = jax.random.normal(key, (kh, kw, cin, cout), jnp.float32) \
        * (2.0 / (kh * kw * cin)) ** 0.5
    return {"w": w, "b": jnp.zeros((cout,), jnp.float32)}


def _bn(c):
    return ({"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))},
            {"mean": jnp.zeros((c,)), "var": jnp.ones((c,))})


def conv2d(p, x, padding="VALID"):
    y = jax.lax.conv_general_dilated(
        x, p["w"], (1, 1), padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["b"]


def batchnorm(p, s, x, train: bool, momentum=0.9, eps=1e-5):
    axes = tuple(range(x.ndim - 1))
    if train:
        m = jnp.mean(x, axes)
        v = jnp.var(x, axes)
        ns = {"mean": momentum * s["mean"] + (1 - momentum) * m,
              "var": momentum * s["var"] + (1 - momentum) * v}
    else:
        m, v, ns = s["mean"], s["var"], s
    y = (x - m) * jax.lax.rsqrt(v + eps) * p["scale"] + p["bias"]
    return y, ns


def _max2(x):
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "VALID")


def _windows(x):
    """``x`` (NHWC) cut to its 2x2 windows, (N, H/2, 2, W/2, 2, C); VALID
    pooling drops an odd last row or column."""
    n, hh, ww, c = x.shape
    h, w = hh // 2, ww // 2
    return x[:, :2 * h, :2 * w, :].reshape(n, h, 2, w, 2, c)


def _pool_pick(x):
    """(max, pick) of each 2x2 window of ``x``: its maximum, and as int8
    the row-major slot (0..3) of the element XLA's ``select_and_scatter``
    picks for ``reduce_window``'s own gradient.  The window is walked in
    row-major order and a later element replaces the one held only where
    the held one is not ``>=`` it, so ties go to the first."""
    win = _windows(x)
    held = win[:, :, 0, :, 0, :]
    pick = jnp.zeros(held.shape, jnp.int8)
    for k, (i, j) in enumerate(((0, 1), (1, 0), (1, 1)), start=1):
        new = win[:, :, i, :, j, :]
        keep = held >= new
        held, pick = jnp.where(keep, held, new), jnp.where(keep, pick, k)
    return held, pick


def _unpool(pick, g, shape):
    """Each entry of ``g`` at the slot ``pick`` names in its window of an
    array of ``shape``, zeros elsewhere and in dropped rows and columns."""
    n, hh, ww, c = shape
    h, w = hh // 2, ww // 2
    full = (n, h, 2, w, 2, c)
    slot = (2 * jax.lax.broadcasted_iota(jnp.int8, (1, 1, 2, 1, 2, 1), 2)
            + jax.lax.broadcasted_iota(jnp.int8, (1, 1, 2, 1, 2, 1), 4))
    dx = jnp.where(
        jnp.broadcast_to(pick[:, :, None, :, None, :], full) == slot,
        jnp.broadcast_to(g[:, :, None, :, None, :], full),
        jnp.zeros((), g.dtype)).reshape(n, 2 * h, 2 * w, c)
    return jnp.pad(dx, ((0, 0), (0, hh - 2 * h), (0, ww - 2 * w), (0, 0)))


def maxpool2_grad(x, g):
    """Cotangent of `maxpool2` at ``x`` for the output cotangent ``g``:
    ``reduce_window``'s own gradient, bit for bit.  The pick is an index
    at the pooled size and the unpooling one select at full size, both
    element-wise, so XLA fuses them into the backward of whatever made
    ``x``; ``select_and_scatter`` would run as an unfused pass over it."""
    return _unpool(_pool_pick(x)[1], g, x.shape)


@jax.custom_vjp
def maxpool2(x):
    """2x2 max pool, stride 2, VALID (NHWC): ``reduce_window``'s forward,
    with `maxpool2_grad`, equal to ``reduce_window``'s own gradient, for
    its backward."""
    return _max2(x)


maxpool2.defvjp(lambda x: (_max2(x), x),
                lambda x, g: (maxpool2_grad(x, g),))


# -------------------------------------------------------------- MNIST CNN ----
def init_mnist_cnn(key, n_classes=10, image_hw=28, widths=(32, 64), fc=512):
    ks = jax.random.split(key, 4)
    p, s = {}, {}
    p["c1"] = _conv(ks[0], 5, 5, 1, widths[0])
    p["bn1"], s["bn1"] = _bn(widths[0])
    p["c2"] = _conv(ks[1], 5, 5, widths[0], widths[1])
    p["bn2"], s["bn2"] = _bn(widths[1])
    hw = ((image_hw - 4) // 2 - 4) // 2      # two valid 5x5 convs + two pools
    p["d1"] = _dense(ks[2], hw * hw * widths[1], fc)
    p["d2"] = _dense(ks[3], fc, n_classes)
    return p, s


def apply_mnist_cnn(p, s, x, train: bool):
    ns = {}
    h = conv2d(p["c1"], x)
    h, ns["bn1"] = batchnorm(p["bn1"], s["bn1"], h, train)
    h = maxpool2(jax.nn.relu(h))
    h = conv2d(p["c2"], h)
    h, ns["bn2"] = batchnorm(p["bn2"], s["bn2"], h, train)
    h = maxpool2(jax.nn.relu(h))
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ p["d1"]["w"] + p["d1"]["b"])
    return h @ p["d2"]["w"] + p["d2"]["b"], ns


# ------------------------------------------------------------ F-MNIST CNN ----
_FM_WIDTHS = (32, 32, 64, 64, 128, 128)


def init_fmnist_cnn(key, n_classes=10, image_hw=28, fc=(382, 192)):
    ks = jax.random.split(key, 9)
    p, s = {}, {}
    cin = 1
    for i, c in enumerate(_FM_WIDTHS):
        p[f"c{i}"] = _conv(ks[i], 3, 3, cin, c)
        p[f"bn{i}"], s[f"bn{i}"] = _bn(c)
        cin = c
    hw = image_hw // 4                       # 'same' convs; pools after pairs 1,2
    flat = hw * hw * _FM_WIDTHS[-1]
    p["d1"] = _dense(ks[6], flat, fc[0])
    p["d2"] = _dense(ks[7], fc[0], fc[1])
    p["d3"] = _dense(ks[8], fc[1], n_classes)
    return p, s


def apply_fmnist_cnn(p, s, x, train: bool):
    ns = {}
    h = x
    for i in range(6):
        h = conv2d(p[f"c{i}"], h, padding="SAME")
        h, ns[f"bn{i}"] = batchnorm(p[f"bn{i}"], s[f"bn{i}"], h, train)
        h = jax.nn.relu(h)
        if i in (1, 3):                      # pools after conv pairs 1 and 2
            h = maxpool2(h)
    h = h.reshape(h.shape[0], -1)
    h = jax.nn.relu(h @ p["d1"]["w"] + p["d1"]["b"])
    h = jax.nn.relu(h @ p["d2"]["w"] + p["d2"]["b"])
    return h @ p["d3"]["w"] + p["d3"]["b"], ns


# -------------------------------------------------------------- IMDb LSTM ----
def init_imdb_lstm(key, vocab=20_000, emb=32, hidden=32, n_classes=2):
    ks = jax.random.split(key, 4)
    return {
        "embed": jax.random.normal(ks[0], (vocab, emb)) * 0.05,
        "wx": jax.random.normal(ks[1], (emb, 4 * hidden)) * emb ** -0.5,
        "wh": jax.random.normal(ks[2], (hidden, 4 * hidden)) * hidden ** -0.5,
        "b": jnp.zeros((4 * hidden,)),
        "out": _dense(ks[3], hidden, n_classes),
    }, {}


def apply_imdb_lstm(p, s, tokens, train: bool):
    """tokens: (B, S) int32.  Final-state LSTM -> dense."""
    x = jnp.take(p["embed"], tokens, axis=0)     # (B, S, E)
    B = x.shape[0]
    H = p["wh"].shape[0]

    def step(carry, xt):
        h, c = carry
        z = xt @ p["wx"] + h @ p["wh"] + p["b"]
        i, f, g, o = jnp.split(z, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)
        return (h, c), None

    init = (jnp.zeros((B, H)), jnp.zeros((B, H)))
    (h, _), _ = jax.lax.scan(step, init, x.transpose(1, 0, 2))
    return h @ p["out"]["w"] + p["out"]["b"], s


# ---------------------------------------------------------------- tiny MLP ---
def init_tiny_mlp(key, n_classes=10, image_hw=16, hidden=32):
    """Beyond-paper micro model for simulation smoke runs: flatten -> dense
    -> relu -> dense.  Small enough that a 100-client fleet jits in seconds
    on CPU (see benchmarks/time_to_accuracy.py, examples/sim_stragglers.py)."""
    k1, k2 = jax.random.split(key)
    return {"d1": _dense(k1, image_hw * image_hw, hidden),
            "d2": _dense(k2, hidden, n_classes)}, {}


def apply_tiny_mlp(p, s, x, train: bool):
    h = x.reshape(x.shape[0], -1)
    h = jax.nn.relu(h @ p["d1"]["w"] + p["d1"]["b"])
    return h @ p["d2"]["w"] + p["d2"]["b"], s


# ----------------------------------------------------------- Reuters DNN -----
def init_reuters_dnn(key, vocab=10_000, n_classes=46, widths=(512, 128)):
    ks = jax.random.split(key, 3)
    p, s = {}, {}
    p["d1"] = _dense(ks[0], vocab, widths[0])
    p["bn1"], s["bn1"] = _bn(widths[0])
    p["d2"] = _dense(ks[1], widths[0], widths[1])
    p["bn2"], s["bn2"] = _bn(widths[1])
    p["d3"] = _dense(ks[2], widths[1], n_classes)
    return p, s


def apply_reuters_dnn(p, s, x, train: bool):
    ns = {}
    h = x @ p["d1"]["w"] + p["d1"]["b"]
    h, ns["bn1"] = batchnorm(p["bn1"], s["bn1"], h, train)
    h = jax.nn.relu(h)
    h = h @ p["d2"]["w"] + p["d2"]["b"]
    h, ns["bn2"] = batchnorm(p["bn2"], s["bn2"], h, train)
    h = jax.nn.relu(h)
    return h @ p["d3"]["w"] + p["d3"]["b"], ns


# ---------------------------------------------------- registry & factories ---
@dataclass(frozen=True)
class SmallNet:
    name: str
    init: callable
    apply: callable
    input_kind: str          # image | tokens | bow
    n_classes: int


def make_smallnet(name: str, **kw) -> SmallNet:
    if name == "mnist_cnn":
        return SmallNet("mnist_cnn", functools.partial(init_mnist_cnn, **kw),
                        apply_mnist_cnn, "image", kw.get("n_classes", 10))
    if name == "fmnist_cnn":
        return SmallNet("fmnist_cnn", functools.partial(init_fmnist_cnn, **kw),
                        apply_fmnist_cnn, "image", kw.get("n_classes", 10))
    if name == "imdb_lstm":
        return SmallNet("imdb_lstm", functools.partial(init_imdb_lstm, **kw),
                        apply_imdb_lstm, "tokens", kw.get("n_classes", 2))
    if name == "reuters_dnn":
        return SmallNet("reuters_dnn", functools.partial(init_reuters_dnn, **kw),
                        apply_reuters_dnn, "bow", kw.get("n_classes", 46))
    if name == "tiny_mlp":
        return SmallNet("tiny_mlp", functools.partial(init_tiny_mlp, **kw),
                        apply_tiny_mlp, "image", kw.get("n_classes", 10))
    raise ValueError(name)
