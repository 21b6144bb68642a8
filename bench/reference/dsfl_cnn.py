"""Plain reference of the DS-FL round (arXiv:2008.06180, Algorithm 1 with
ERA, Eq. 13) on the paper's MNIST CNN, in straightforward `jax.numpy`.

Imports nothing of the system under test.  It follows the paper's round:
every client trains E epochs of minibatch SGD on its private data, predicts
softmax probabilities on the round's open batch o_r, the server averages
them and sharpens the mean with a softmax at temperature T (ERA), and then
every client and the server model distill E' epochs on (o_r, teacher).
The random choices follow the documented key schedule of the system under
test, so that the same seed draws the same o_r and the same minibatch
orders: per round ``rng, rk, ri = split(rng, 3)``, o_r =
``choice(ri, |open|, (|o_r|,), replace=False)``, ``r1, r2, r3, r4 =
split(rk, 4)``, client keys ``split(r1, K)`` (update) and ``split(r2, K)``
(distillation), the server's key ``r4``; each loop splits its key into one
per epoch and permutes the rows with it.

``dtype=float32`` runs every convolution and matrix product at HIGHEST
precision: the reference.  ``dtype=bfloat16`` keeps parameters,
activations and updates in bfloat16: the control, one precision below the
configuration's float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


# -------------------------------------------------------------- model ----
def init_cnn(key, widths=(32, 64), fc=512, n_classes=10, hw=28):
    """He-normal weights in the parameter layout the system reads:
    conv5x5(w0) - BN - relu - pool2 - conv5x5(w1) - BN - relu - pool2 -
    dense(fc) - relu - dense(classes); BN running statistics as state."""
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def conv(k, cin, cout):
        return {"w": jax.random.normal(k, (5, 5, cin, cout), F32)
                * (2.0 / (25 * cin)) ** 0.5, "b": jnp.zeros((cout,), F32)}

    def dense(k, n_in, n_out):
        return {"w": jax.random.normal(k, (n_in, n_out), F32)
                * (2.0 / n_in) ** 0.5, "b": jnp.zeros((n_out,), F32)}

    def bn(c):
        return ({"scale": jnp.ones((c,), F32), "bias": jnp.zeros((c,), F32)},
                {"mean": jnp.zeros((c,), F32), "var": jnp.ones((c,), F32)})

    side = ((hw - 4) // 2 - 4) // 2
    p, s = {}, {}
    p["c1"] = conv(k1, 1, widths[0])
    p["bn1"], s["bn1"] = bn(widths[0])
    p["c2"] = conv(k2, widths[0], widths[1])
    p["bn2"], s["bn2"] = bn(widths[1])
    p["d1"] = dense(k3, side * side * widths[1], fc)
    p["d2"] = dense(k4, fc, n_classes)
    return p, s


def _prec(dt):
    return HIGHEST if dt == F32 else None


def apply_cnn(p, s, x, train: bool, dt=F32):
    prec = _prec(dt)

    def conv(q, h):
        """A 'valid' convolution as one matrix product over the k x k
        patches (HWIO weights): plain, and at HIGHEST it compiles in
        seconds where the TPU compiler's multi-pass convolution takes
        many minutes."""
        kh, kw, c, o = q["w"].shape
        n, hh, ww, _ = h.shape
        ho, wo = hh - kh + 1, ww - kw + 1
        cols = jnp.stack([h[:, i:i + ho, j:j + wo, :]
                          for i in range(kh) for j in range(kw)], axis=3)
        y = jnp.dot(cols.reshape(n, ho, wo, kh * kw * c),
                    q["w"].reshape(kh * kw * c, o), precision=prec)
        return y + q["b"]

    def bn(q, st, h):
        if train:
            m = jnp.mean(h, axis=(0, 1, 2))
            v = jnp.mean(jnp.square(h - m), axis=(0, 1, 2))
            ns = {"mean": 0.9 * st["mean"] + 0.1 * m,
                  "var": 0.9 * st["var"] + 0.1 * v}
        else:
            m, v, ns = st["mean"], st["var"], st
        return (h - m) / jnp.sqrt(v + 1e-5) * q["scale"] + q["bias"], ns

    def pool(h):
        n, hh, ww, c = h.shape
        return h.reshape(n, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))

    ns = {}
    h = x.astype(dt)
    h, ns["bn1"] = bn(p["bn1"], s["bn1"], conv(p["c1"], h))
    h = pool(jnp.maximum(h, 0))
    h, ns["bn2"] = bn(p["bn2"], s["bn2"], conv(p["c2"], h))
    h = pool(jnp.maximum(h, 0))
    h = h.reshape(h.shape[0], -1)
    h = jnp.maximum(jnp.dot(h, p["d1"]["w"], precision=prec) + p["d1"]["b"],
                    0)
    return jnp.dot(h, p["d2"]["w"], precision=prec) + p["d2"]["b"], ns


def log_softmax(z):
    z = z - jnp.max(z, axis=-1, keepdims=True)
    return z - jnp.log(jnp.sum(jnp.exp(z), axis=-1, keepdims=True))


# -------------------------------------------------------------- round ----
def _train(p, s, x, target, key, epochs: int, bs: int, lr: float, dt, soft):
    """E epochs of minibatch SGD; ``target`` is labels (hard) or teacher
    probabilities (soft, the distillation loss).  Returns the mean over
    epochs of each epoch's mean batch loss."""
    n = x.shape[0]
    nb = n // bs

    def batch(carry, idx):
        p, s = carry
        xb, tb = x[idx], target[idx]

        def loss(p):
            logits, ns = apply_cnn(p, s, xb, True, dt)
            ls = log_softmax(logits)
            if soft:
                ce = -jnp.sum(tb.astype(ls.dtype) * ls, axis=-1)
            else:
                ce = -jnp.take_along_axis(ls, tb[:, None], axis=-1)[:, 0]
            return jnp.mean(ce), ns

        (l, ns), g = jax.value_and_grad(loss, has_aux=True)(p)
        p = jax.tree.map(lambda a, b: (a - lr * b).astype(dt), p, g)
        return (p, ns), l.astype(F32)

    def epoch(carry, ek):
        perm = jax.random.permutation(ek, n)[:nb * bs].reshape(nb, bs)
        carry, ls = jax.lax.scan(batch, carry, perm)
        return carry, jnp.mean(ls)

    (p, s), el = jax.lax.scan(epoch, (p, s), jax.random.split(key, epochs))
    return p, s, jnp.mean(el)


def _entropy(q):
    return -jnp.sum(q * jnp.log(jnp.clip(q, 1e-12, 1.0)), axis=-1)


@functools.partial(jax.jit, static_argnames=("hp", "dt"))
def dsfl_round(wk, sk, wg, sg, x, y, open_x, o_idx, rk, hp, dt=F32):
    """One round.  ``hp`` = (local_epochs, distill_epochs, batch, lr,
    lr_distill, temperature).  Returns the new (wk, sk, wg, sg) and the
    round's losses."""
    e_loc, e_dis, bs, lr, lr_d, temp = hp
    K = x.shape[0]
    r1, r2, _r3, r4 = jax.random.split(rk, 4)
    xo = open_x[o_idx]
    bs_d = min(bs, xo.shape[0])
    # clients one after another (`lax.map`): a small program that compiles
    # fast and holds one client's activations at a time
    wk, sk, up = jax.lax.map(
        lambda a: _train(a[0], a[1], a[2], a[3], a[4], e_loc, bs, lr, dt,
                         False), (wk, sk, x, y, jax.random.split(r1, K)))
    probs = jax.lax.map(lambda a: jax.nn.softmax(
        apply_cnn(a[0], a[1], xo, False, dt)[0].astype(F32), axis=-1),
        (wk, sk))
    teacher = jax.nn.softmax(jnp.mean(probs, axis=0) / temp, axis=-1)
    wk, sk, dl = jax.lax.map(
        lambda a: _train(a[0], a[1], xo, teacher, a[2], e_dis, bs_d, lr_d, dt,
                         True), (wk, sk, jax.random.split(r2, K)))
    wg, sg, gl = _train(wg, sg, xo, teacher, r4, e_dis, bs_d, lr_d, dt, True)
    losses = {"update_loss": jnp.mean(up), "distill_loss": jnp.mean(dl),
              "server_distill_loss": gl,
              "global_entropy": jnp.mean(_entropy(teacher))}
    return (wk, sk, wg, sg), losses


def run_rounds(w0, data, seed: int, hp: tuple, open_batch: int,
               n_rounds: int, dt=F32):
    """``n_rounds`` rounds from the initial weights ``w0 = (wk, sk, wg,
    sg)``.  Returns the per-round losses and the (client, server)
    parameters after each round."""
    cast = (lambda t: jax.tree.map(lambda a: a.astype(dt), t))
    wk, sk, wg, sg = (cast(t) for t in w0)
    x, y, open_x = data
    n_open = open_x.shape[0]
    rng = jax.random.PRNGKey(seed)
    losses, params = [], []
    for _ in range(n_rounds):
        rng, rk, ri = jax.random.split(rng, 3)
        o_idx = jax.random.choice(ri, n_open, (min(open_batch, n_open),),
                                  replace=False)
        (wk, sk, wg, sg), m = dsfl_round(wk, sk, wg, sg, x, y, open_x, o_idx,
                                         rk, hp, dt)
        losses.append({k: float(v) for k, v in m.items()})
        params.append((wk, wg))
    return losses, params
