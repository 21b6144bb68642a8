"""The paper's DS-FL round on its small image nets, run by the system under
test: `FedEngine` driving `DSFLAlgorithm` (ERA through the Pallas kernel).

The benchmark makes the inputs and the weights from the seed, on the
device, in jitted calls: synthetic 28x28 digits (smooth class templates,
shifted and noised), split into the paper's non-IID shards (two label
shards per client), and He-normal weights from `reference.dsfl_cnn`.

The check (see `check`) compares the first ``check_steps`` rounds that
set-up drove through the window's own call with the plain reference, run
after the window from the same weights and data:

- ``loss_gap``: the largest relative gap of a round's losses (client
  update, client distillation, server distillation, teacher entropy);
- ``update1_gap``: the first round's parameter update, by the worst leaf:
  the gap between the program's and the reference's update norms over the
  reference's norm of that leaf or of the median leaf, whichever is larger;
- ``change3_gap``: the same for the parameters' change after round 3.

Leaves whose reference update is under a thousandth of the median leaf's
(the conv biases in front of a batch norm, which a batch norm makes
gradient-free) move by round-off alone and are left out of both.
"""
from __future__ import annotations

import gc
import math
import os
import sys
from dataclasses import dataclass, field
from types import SimpleNamespace

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from reference import dsfl_cnn as ref  # noqa: E402


# ---------------------------------------------------------------- data ----
def make_inputs(cfg: dict, seed: int):
    """(x_clients, y_clients, open_x, x_test, y_test) and the initial
    weights (wk, sk, wg, sg), made on the device from ``seed``."""
    import jax
    import jax.numpy as jnp
    K, C, hw = cfg["clients"], cfg["n_classes"], cfg["image_hw"]
    n_priv, n_open, n_test = (cfg["n_private"], cfg["n_open"],
                              cfg["n_test"])
    shards = cfg["shards_per_client"]
    widths, fc = tuple(cfg["conv_widths"]), cfg["fc_width"]

    def draw(key, labels, tmpl):
        k1, k2 = jax.random.split(key)
        sh = jax.random.randint(k1, (labels.shape[0], 2), -2, 3)
        img = jax.vmap(lambda t, s: jnp.roll(t, (s[0], s[1]), axis=(0, 1)))(
            tmpl[labels], sh)
        img = img + 0.3 * jax.random.normal(k2, img.shape)
        return jnp.clip(img, 0.0, 1.0)[..., None]

    @jax.jit
    def make(key):
        kt, kp, ko, kte, ks, kw = jax.random.split(key, 6)
        base = jax.random.normal(kt, (C, 7, 7))
        tmpl = jax.image.resize(base, (C, hw, hw), "bilinear")
        lo = tmpl.min(axis=(1, 2), keepdims=True)
        hi = tmpl.max(axis=(1, 2), keepdims=True)
        tmpl = (tmpl - lo) / (hi - lo)
        y_priv = jnp.arange(n_priv) // (n_priv // C)        # label-sorted
        x_priv = draw(kp, y_priv, tmpl)
        n_sh = K * shards
        size = n_priv // n_sh
        own = jax.random.permutation(ks, n_sh).reshape(K, shards)
        idx = (own[..., None] * size + jnp.arange(size)).reshape(K, -1)
        ko1, ko2 = jax.random.split(ko)
        kt1, kt2 = jax.random.split(kte)
        open_x = draw(ko2, jax.random.randint(ko1, (n_open,), 0, C), tmpl)
        y_test = jax.random.randint(kt1, (n_test,), 0, C)
        x_test = draw(kt2, y_test, tmpl)
        init = lambda k: ref.init_cnn(k, widths, fc, C, hw)
        wg, sg = init(kw)
        wk, sk = jax.vmap(init)(jax.random.split(kw, K))
        return ((x_priv[idx], y_priv[idx].astype(jnp.int32), open_x, x_test,
                 y_test.astype(jnp.int32)), (wk, sk, wg, sg))

    return make(jax.random.PRNGKey(seed))


def _hp(cfg):
    return (cfg["local_epochs"], cfg["distill_epochs"], cfg["batch_size"],
            cfg["lr"], cfg["lr_distill"], cfg["temperature"])


# --------------------------------------------------------------- setup ----
@dataclass
class Obj:
    eng: object
    task: object
    state: object
    w0: tuple
    data: tuple
    rounds_per_call: int
    snaps: dict = field(default_factory=dict)


def setup(cfg: dict, traffic: dict, seed: int, devices) -> Obj:
    import jax
    from repro.core.algorithms import DSFLAlgorithm
    from repro.core.engine import FedEngine, make_eval_fn
    from repro.core.protocol import DSFLConfig
    from repro.models.smallnets import apply_mnist_cnn

    if cfg["optimizer"] != "sgd":
        raise ValueError("the reference follows SGD only; got "
                         f"{cfg['optimizer']!r}")
    data, w0 = make_inputs(cfg, seed)
    jax.block_until_ready((data, w0))
    xc, yc, open_x, x_test, y_test = data
    hp = DSFLConfig(local_epochs=cfg["local_epochs"],
                    distill_epochs=cfg["distill_epochs"],
                    batch_size=cfg["batch_size"], open_batch=cfg["open_batch"],
                    lr=cfg["lr"], lr_distill=cfg["lr_distill"],
                    optimizer=cfg["optimizer"], aggregation=cfg["aggregation"],
                    temperature=cfg["temperature"], seed=seed)
    algo = DSFLAlgorithm(apply_mnist_cnn, hp, use_kernel=cfg["era_kernel"])
    eng = FedEngine(algo, make_eval_fn(apply_mnist_cnn, x_test, y_test))
    task = SimpleNamespace(x_clients=xc, y_clients=yc, open_x=open_x)
    state = algo.init_from(*w0)
    return Obj(eng, task, state, w0, (xc, yc, open_x),
               int(traffic["rounds_per_call"]))


def step(obj: Obj) -> None:
    """One call of the window: ``rounds_per_call`` rounds fused in one
    `FedEngine.run`, with the test-set eval at its end."""
    import jax
    k = obj.rounds_per_call
    obj.state = obj.eng.run(obj.state, obj.task, rounds=k, chunk_rounds=k,
                            log_every=k)
    jax.block_until_ready(obj.state)


def record(obj: Obj, i: int) -> None:
    obj.snaps[i * obj.rounds_per_call] = (obj.state.clients.params,
                                          obj.state.server.params)


def failed_rounds(obj: Obj) -> int:
    import math
    return sum(1 for rec in obj.eng.history
               if not all(math.isfinite(v) for v in rec.values()))


# --------------------------------------------------------------- check ----
LOSSES = ("update_loss", "distill_loss", "server_distill_loss",
          "global_entropy")


def _leaf_norms(a, b) -> list:
    """Per-leaf L2 norm of a - b (float64 on the host)."""
    import jax
    import numpy as np
    return [float(np.linalg.norm(np.asarray(x, np.float64)
                                 - np.asarray(y, np.float64)))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))]


def leaf_names(tree) -> list:
    import jax
    return [jax.tree_util.keystr(path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def norm_gaps(prog: list, refn: list, keep: list) -> list:
    """Per-leaf gap of norms: |prog - ref| over max(ref leaf, median ref
    leaf); None for a leaf left out."""
    import numpy as np
    med = float(np.median([r for r, k in zip(refn, keep) if k]))
    return [abs(p - r) / max(r, med) if k else None
            for p, r, k in zip(prog, refn, keep)]


def _worst(gaps: list, names: list, what: str) -> float:
    i = max((i for i, g in enumerate(gaps) if g is not None),
            key=lambda i: gaps[i])
    kept = sorted(g for g in gaps if g is not None)
    print(f"bench: {what}: worst leaf {names[i]} {gaps[i]:.3e}, median "
          f"leaf {kept[len(kept) // 2]:.3e}", file=sys.stderr)
    return gaps[i]


def compare(hist: list, snaps: dict, ref_losses: list, ref_params: list,
            w0: tuple) -> dict:
    """The three compared numbers from the program's first rounds (history
    and parameter snapshots) and the reference's."""
    import numpy as np
    n = len(ref_losses)
    if len(hist) < n:       # rounds that logged no losses
        loss_gap = math.inf
    else:
        loss_gap = max(abs(hist[r][k] - ref_losses[r][k])
                       / max(abs(ref_losses[r][k]), 1e-12)
                       for r in range(n) for k in LOSSES)
    base = (w0[0], w0[2])
    names = leaf_names({"clients": w0[0], "server": w0[2]})
    r1 = _leaf_norms(ref_params[0], base)
    med = float(np.median(r1))
    keep = [r >= 1e-3 * med for r in r1]
    out = {"loss_gap": loss_gap,
           "update1_gap": _worst(norm_gaps(_leaf_norms(snaps[1], base), r1,
                                           keep), names, "update1")}
    if n >= 3 and 3 in snaps:
        out["change3_gap"] = _worst(norm_gaps(
            _leaf_norms(snaps[3], base), _leaf_norms(ref_params[2], base),
            keep), names, "change3")
    return out


def reference(obj: Obj, cfg: dict, seed: int, n: int, dt=None):
    import jax.numpy as jnp
    return ref.run_rounds(obj.w0, obj.data, seed, _hp(cfg),
                          cfg["open_batch"], n, dt or jnp.float32)


def check(obj: Obj, cfg: dict, traffic: dict, seed: int,
          control: bool = False) -> list:
    """Frees the program's state, runs the reference over the check's
    rounds and returns [(name, value, limit)].  With ``control``, the
    control (the reference in bfloat16, put in the program's place) is
    compared the same way, as ``control.<name>``."""
    import jax.numpy as jnp
    n = max(obj.snaps)
    hist = obj.eng.history[:n]
    snaps = obj.snaps
    obj.eng = obj.state = obj.task = None
    gc.collect()
    ref_losses, ref_params = reference(obj, cfg, seed, n)
    got = compare(hist, snaps, ref_losses, ref_params, obj.w0)
    if control:
        c_losses, c_params = reference(obj, cfg, seed, n, jnp.bfloat16)
        c_snaps = {i + 1: p for i, p in enumerate(c_params)}
        got.update({"control." + k: v for k, v in compare(
            c_losses, c_snaps, ref_losses, ref_params, obj.w0).items()})
    lim = cfg["check"]["limits"]
    return [(k, v, lim[k.split(".")[-1]]) for k, v in got.items()]


# ------------------------------------------------------ counts (shapes) ----
def cnn_forward_flops(cfg: dict) -> int:
    """FLOPs of one image through the CNN's convolutions and dense layers
    (2 per multiply-add; element-wise work left out)."""
    hw, (w1, w2), fc, C = (cfg["image_hw"], cfg["conv_widths"],
                           cfg["fc_width"], cfg["n_classes"])
    o1 = hw - 4
    p1 = o1 // 2
    o2 = p1 - 4
    p2 = o2 // 2
    macs = (o1 * o1 * w1 * 25 * 1 + o2 * o2 * w2 * 25 * w1
            + p2 * p2 * w2 * fc + fc * C)
    return 2 * macs


def round_flops(cfg: dict) -> float:
    """FLOPs one round requires, counted from shapes: a training image
    costs three forwards (forward, and the backward's two products), a
    prediction or test image one.  Per client: E local epochs over its
    private rows, E' distillation epochs over o_r, one prediction over o_r;
    the server distills as a client does; the eval scores the test set."""
    f = cnn_forward_flops(cfg)
    K = cfg["clients"]
    n_k = cfg["n_private"] // K
    o = cfg["open_batch"]
    train = K * (cfg["local_epochs"] * n_k + cfg["distill_epochs"] * o) \
        + cfg["distill_epochs"] * o
    infer = K * o + cfg["n_test"]
    return float(3 * f * train + f * infer)


def era_kernel_bytes(cfg: dict) -> float:
    """HBM bytes of one ERA kernel call: read the (K, |o_r|, C) f32 client
    probabilities, write the (|o_r|, C) f32 teacher."""
    K, o, C = cfg["clients"], cfg["open_batch"], cfg["n_classes"]
    return float(4 * (K * o * C + o * C))


def era_kernel_flops(cfg: dict) -> float:
    """Element-wise work of one ERA call: the K-way sum, and the softmax's
    scale, exp, sum and divide per teacher entry."""
    K, o, C = cfg["clients"], cfg["open_batch"], cfg["n_classes"]
    return float(K * o * C + 5 * o * C)
