"""The LLM DS-FL round's plain reference (``bench/reference/llm_dsfl.py``,
which decides the benchmark cell's ``correct``): its layer-by-layer
backward, with the head's loss taken in blocks of tokens, against
``jax.grad`` of the same loss over its own whole forward."""
from __future__ import annotations

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import llm_dsfl as R  # noqa: E402
from reference import qwen  # noqa: E402

F32 = jnp.float32
CFG = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
           num_attention_heads=4, num_key_value_heads=4, head_dim=16,
           vocab_size=256, initializer_range=0.2, rope_theta=1e6,
           rms_norm_eps=1e-5)
# the update is theta - lr * grad in float32: a power of two so large
# that the step, not theta, sets the rounding, and the gradient read back
# from it keeps float32's ~1e-7
LR = 2.0 ** 20
GAMMA = 0.7


def _full_loss(params, toks, labels, teacher, c, n_p):
    """The client's loss over the whole batch at once: cross-entropy on
    the private rows plus GAMMA times distillation on the open rows."""
    x = jnp.take(params["embed"]["tok"], toks, axis=0)
    for i in range(CFG["num_hidden_layers"]):
        x = qwen._layer(x, jax.tree.map(lambda a: a[i], params["blocks"]), c,
                        "f32")
    z = qwen._head(x.reshape(-1, x.shape[-1]), params["embed"]["tok"],
                   params["final_norm"]["scale"], c, "f32")
    lse = jax.nn.logsumexp(z, axis=-1)
    ce = jnp.mean(lse[:n_p] - jnp.take_along_axis(
        z[:n_p], labels[:, None], axis=-1)[:, 0])
    kd = jnp.mean(lse[n_p:] - jnp.sum(teacher * z[n_p:], axis=-1))
    return ce + GAMMA * kd


def test_layer_by_layer_backward_matches_full_gradient():
    c = qwen.dims(CFG)
    V, S = CFG["vocab_size"], 16
    kp, kt, ko, kw = jax.random.split(jax.random.PRNGKey(3), 4)
    private = np.asarray(jax.random.randint(kp, (2, S), 0, V))
    open_ = np.asarray(jax.random.randint(ko, (2, S), 0, V))
    toks = jnp.asarray(np.concatenate([private, open_]))
    labels = jnp.asarray(np.concatenate([private[:, 1:], private[:, -1:]],
                                        axis=1).reshape(-1))
    n_p = n_o = private.size
    teacher = jax.nn.softmax(3.0 * jax.random.normal(kt, (n_o, V)), axis=-1)
    init = jax.jit(lambda k: qwen.init_params(k, CFG, F32))
    with jax.default_matmul_precision("highest"):
        want_loss, want = jax.value_and_grad(_full_loss)(
            init(kw), toks, labels, teacher, c, n_p)
        cl = R.Client(CFG, init, kw, jax.devices()[0])
        old = jax.tree.map(np.asarray, (cl.layers, cl.tok, cl.scale))
        blk = 8      # four blocks of private rows, four of open rows
        acts, hid = R._forward([cl], [toks], c, "f32")
        (loss,), _ = R._client_step(
            [cl], acts, hid, [toks], [labels],
            [[teacher[sl] for sl in R._blocks(n_o, blk)]], n_p, n_o, blk,
            GAMMA, LR, c, "f32")
    new = (cl.layers, cl.tok, cl.scale)
    got = jax.tree.map(lambda a, b: (a - np.asarray(b, np.float64)) / LR,
                       jax.tree.map(lambda a: a.astype(np.float64), old), new)
    layers = jax.tree.map(lambda *ls: np.stack(ls), *got[0])
    assert abs(loss - float(want_loss)) <= 1e-5 * abs(float(want_loss))
    pairs = [(layers, want["blocks"]), (got[1], want["embed"]["tok"]),
             (got[2], want["final_norm"]["scale"])]
    for g, w in pairs:
        for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
            b = np.asarray(b, np.float64)
            assert np.linalg.norm(a - b) <= 1e-5 * np.linalg.norm(b)
