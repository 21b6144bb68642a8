"""The LLM DS-FL round on a Qwen1.5 decoder, run by the system under test:
`LLMDSFLAlgorithm` (`core.llm_dsfl`) on `FedEngine`, K clients with the
client axis on the mesh axis "pod" (`launch.mesh.make_client_mesh`: one
client per chip when there are K chips), dense bf16 uploads through
`wire.FP16Codec`, the round state donated: as `launch.train.
build_federated` builds it, one round per `FedEngine.run` call under the
CLI's `axis_ctx`.  The algorithm's ``probe_rows`` has every round report
the exchanged client mean at a few seed-drawn open-batch tokens.

The data is the CLI's (`data.pipeline.build_lm_task` from the seed: client
k's private sequences from domain k's chain, the open set from seven).
The initial weights are `reference.qwen.init_params` of one key per
client drawn from the seed, made on the chips in the engine's own
parameter placement, so that program and reference start from the same
bf16 weights.

The check compares the program's first ``check_steps`` rounds, which
set-up drove through the window's own call, with the plain float32
reference (`reference.llm_dsfl`), run after the window from the same
weights and data, each client on its own chip:

- ``loss_gap``: the largest relative gap of a round's loss (the mean
  over clients of cross-entropy plus gamma times distillation);
- ``mean_tv_gap``: what the exchange delivered in round 1, the clients'
  float32 mean prediction that ERA sharpens, at a seed-drawn sample of
  open-batch tokens (the round's own ``client_mean`` metric), by the
  largest total-variation distance.  The teacher itself is not compared:
  at this vocabulary ERA's teacher is uniform to seven digits whatever
  the clients upload;
- ``update1_gap`` and ``change3_gap``: the parameters' change after round
  1 and after the last check round, by the worst leaf (a client's tensor
  in one layer): the gap between the program's and the reference's norms
  of the change, over the reference's norm of that leaf or of the median
  leaf, whichever is larger;
- ``logit_gap``: after the last check round, each client's logits on a
  seed-drawn probe of open-batch tokens (the program's forward taken at
  `record`): the widest gap by which the reference's logit of the
  program's top token lies below the reference's best.

With ``control`` the control (the reference in fp8, put in the program's
place) is compared the same way, as ``control.<name>``.
"""
from __future__ import annotations

import gc
import math
import os
import sys
from dataclasses import dataclass, field

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from families.qwen_serve import model_config, param_bytes  # noqa: E402
from reference import llm_dsfl as ref  # noqa: E402
from reference import qwen  # noqa: E402


def client_keys(seed: int, K: int):
    """One key of initial weights per client."""
    import jax
    return jax.random.split(jax.random.fold_in(jax.random.PRNGKey(seed), 1),
                            K)


def sample_rows(seed: int, n_tokens: int, n: int, salt: int):
    """``n`` distinct flat open-batch token indices drawn from the seed."""
    import numpy as np
    rng = np.random.default_rng([seed, salt])
    return np.sort(rng.choice(n_tokens, size=n, replace=False))


@dataclass
class Obj:
    eng: object
    task: object
    state: object
    cfg: dict
    keys: object
    devices: list
    n_check: int
    mean_rows: object           # flat open-set token indices
    probe_rows: object
    norms_fn: object
    mean: object = None         # round 1's client mean at ``mean_rows``
    probe_top: object = None
    norms: dict = field(default_factory=dict)


def _hp(cfg: dict, seed: int):
    from repro.core.llm_dsfl import LLMDsflHP
    return LLMDsflHP(lr=cfg["lr"], gamma=cfg["gamma"],
                     aggregation=cfg["aggregation"],
                     temperature=cfg["temperature"], topk=None, rounds=1,
                     seed=seed, open_batch=cfg["open_batch"])


def _init(cfg: dict):
    """The clients' stacked initial weights from their keys."""
    import jax
    import jax.numpy as jnp
    return jax.vmap(lambda k: qwen.init_params(k, cfg,
                                               jnp.dtype(cfg["dtype"])))


def first_open_order(seed: int, n_open: int, n_r: int):
    """The open-set sequences of round 1's open batch o_r, in its order:
    `FedEngine.run`'s first draw (its key stream from ``hp.seed``: split
    into (rng, round key, o_r key), o_r without replacement)."""
    import jax
    import numpy as np
    _, _, ri = jax.random.split(jax.random.PRNGKey(seed), 3)
    return np.asarray(jax.random.choice(ri, n_open, (n_r,), replace=False))


def norms_program(cfg: dict, sharding):
    """(params, keys) -> per client, `reference.llm_dsfl.delta_norms` of
    its parameters from its initial ones, which the program makes again
    from the keys in the parameters' placement: {leaf: (K, L) or (K,)}."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    by_client = NamedSharding(jax.tree.leaves(sharding)[0].mesh, P("pod"))

    def f(params, keys):
        keys = jax.lax.with_sharding_constraint(keys, by_client)
        p0 = jax.lax.with_sharding_constraint(_init(cfg)(keys), sharding)
        return jax.vmap(ref.delta_norms)(params, p0)

    return jax.jit(f)


def setup(cfg: dict, traffic: dict, seed: int, devices) -> Obj:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import wire
    from repro.core.engine import FedEngine
    from repro.core.llm_algorithms import LLMDSFLAlgorithm
    from repro.data.pipeline import build_lm_task
    from repro.launch.mesh import make_client_mesh

    K, B, Bo, S = (cfg["clients"], cfg["private_batch"], cfg["open_batch"],
                   cfg["seq_len"])
    mc = model_config(cfg)
    n = Bo * S
    mean_rows = sample_rows(seed, n, cfg["check"]["mean_positions"], 0)
    # the same tokens in round 1's o_r, a permutation of the open set
    where = np.argsort(first_open_order(seed, Bo, Bo))
    in_batch = where[mean_rows // S] * S + mean_rows % S
    algo = LLMDSFLAlgorithm(mc, _hp(cfg, seed),
                            probe_rows=tuple(int(r) for r in in_batch))
    eng = FedEngine(algo, codec=wire.FP16Codec(),
                    mesh=make_client_mesh(K, devices=devices),
                    donate_state=True)
    task = build_lm_task(seed, K, B, S, mc.vocab, n_open=Bo)
    keys = client_keys(seed, K)
    shapes = jax.eval_shape(_init(cfg), keys)
    ctx = eng.make_ctx(task, o_idx=jnp.zeros((Bo,), jnp.int32))
    st_sh, _ = algo.shardings(eng.mesh, algo.init_from(shapes), ctx)
    sharding = st_sh.clients.params
    state = algo.init_from(jax.jit(_init(cfg), out_shardings=sharding)(keys))
    jax.block_until_ready((state, task))
    if len(devices) >= K:
        owner = placement(state.clients.params)
        if sorted(owner) != list(range(K)) or len(
                {d for ds in owner.values() for d in ds}) != K or any(
                len(ds) != 1 for ds in owner.values()):
            raise AssertionError(f"not one client per chip: {owner}")
        print(f"bench: placement on every parameter leaf, client -> chip "
              f"{owner}", file=sys.stderr)
    return Obj(eng, task, state, cfg, keys, list(devices),
               int(traffic["check_steps"]), mean_rows,
               sample_rows(seed, n, cfg["check"]["probe_positions"], 1),
               norms_program(cfg, sharding))


def placement(params) -> dict:
    """Client -> the ids of the devices holding its shards, the same on
    every parameter leaf; raises where a shard holds more than one
    client."""
    import jax
    owner = None
    for leaf in jax.tree.leaves(params):
        by_client = {}
        for sh in leaf.addressable_shards:
            c = sh.index[0]
            lo, hi = c.start or 0, leaf.shape[0] if c.stop is None else c.stop
            if hi - lo != 1:
                raise AssertionError(f"a shard of {leaf.shape} holds "
                                     f"clients {lo}..{hi - 1}")
            by_client.setdefault(lo, set()).add(sh.device.id)
        placed = {k: tuple(sorted(v)) for k, v in by_client.items()}
        owner = owner or placed
        if placed != owner:
            raise AssertionError(f"placement differs by leaf: {placed} "
                                 f"against {owner}")
    return owner


def step(obj: Obj) -> None:
    """One call of the window: one round through `FedEngine.run`."""
    import jax
    from repro.models.shardctx import axis_ctx
    with axis_ctx(obj.eng.mesh, batch_axes=("data",)):
        obj.state = obj.eng.run(obj.state, obj.task, rounds=1,
                                overlap=obj.cfg["overlap"])
    jax.block_until_ready(obj.state)


def _rows(flat, S: int):
    import jax.numpy as jnp
    return jnp.asarray(flat // S), jnp.asarray(flat % S)


def probe_top(obj: Obj):
    """Each client's top token at the probe rows, by the system's own
    forward (`models.api.model_logits`) on the clients' current weights:
    (K, n) on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models.api import model_logits
    from repro.models.shardctx import axis_ctx
    mc = obj.eng.algo.cfg
    seq, pos = _rows(obj.probe_rows, obj.cfg["seq_len"])

    def top(params, open_x):
        def one(p):
            z, _ = model_logits(mc, p, open_x)
            return jnp.argmax(z[seq, pos], axis=-1)
        return jax.vmap(one)(params)

    with axis_ctx(obj.eng.mesh, batch_axes=("data",)):
        return np.asarray(jax.jit(top)(obj.state.clients.params,
                                       obj.task.open_x))


def record(obj: Obj, i: int) -> None:
    """Keeps what the check compares: after round 1 the exchanged mean
    the round reported, after rounds 1 and ``n_check`` the norms of the
    parameters' change, after round ``n_check`` the top tokens."""
    import jax
    import numpy as np
    if i == 1:
        m = obj.eng.last_metrics.get("client_mean")
        obj.mean = None if m is None else np.asarray(m)
    if i in (1, obj.n_check):
        obj.norms[i] = jax.tree.map(np.asarray, obj.norms_fn(
            obj.state.clients.params, obj.keys))
    if i == obj.n_check:
        obj.probe_top = probe_top(obj)


def failed_rounds(obj: Obj) -> int:
    return sum(1 for rec in obj.eng.history
               if not all(math.isfinite(v) for v in rec.values()))


# --------------------------------------------------------------- check ----
def leaf_norms(norms) -> tuple:
    """Flat (labels, values) of a run's norms: the program's {leaf: (K, L)
    or (K,)} or the reference's per-client list of {leaf: (L,) or ()}."""
    import numpy as np
    if isinstance(norms, dict):
        norms = [{k: v[c] for k, v in norms.items()}
                 for c in range(len(next(iter(norms.values()))))]
    labels, values = [], []
    for c, per in enumerate(norms):
        for name in sorted(per):
            for layer, v in enumerate(np.atleast_1d(per[name])):
                labels.append(f"client {c} {name} layer {layer}")
                values.append(float(v))
    return labels, values


def norm_gap(prog, want, what: str) -> float:
    """The worst leaf's |prog - ref| over max(ref leaf, median ref leaf),
    leaves whose reference change is under a thousandth of the median's
    left out; inf where the program's norms are missing."""
    import numpy as np
    if prog is None:
        return math.inf
    labels, r = leaf_norms(want)
    _, p = leaf_norms(prog)
    med = float(np.median(r))
    gaps = [abs(a - b) / max(b, med) if b >= 1e-3 * med else None
            for a, b in zip(p, r)]
    i = max((i for i, g in enumerate(gaps) if g is not None),
            key=lambda i: gaps[i])
    kept = sorted(g for g in gaps if g is not None)
    print(f"bench: {what}: worst leaf {labels[i]} {gaps[i]:.3e}, median "
          f"leaf {kept[len(kept) // 2]:.3e}", file=sys.stderr)
    return gaps[i]


def compare(losses: list, mean, norms: dict, top, want: dict,
            n: int) -> dict:
    """The compared numbers from a run's round losses, round 1's client
    mean at the mean rows, its norms of the change after rounds 1 and
    ``n`` and its top tokens at the probe, against the reference's."""
    import numpy as np
    if len(losses) < len(want["losses"]):   # rounds that logged no loss
        loss_gap = math.inf
    else:
        loss_gap = max(abs(a - b) / max(abs(b), 1e-12)
                       for a, b in zip(losses, want["losses"]))
    if mean is None:
        mean_tv = math.inf
    else:
        mean_tv = float((0.5 * np.abs(
            np.asarray(mean, np.float64)
            - np.asarray(want["mean"], np.float64)).sum(-1)).max())
    if top is None:
        logit_gap = math.inf
    else:
        logit_gap = max(float(qwen.gaps(z, np.asarray(t)).max())
                        for z, t in zip(want["probe"], top))
    out = {"loss_gap": loss_gap, "mean_tv_gap": mean_tv,
           "update1_gap": norm_gap(norms.get(1), want["norms"][1],
                                   "update1"),
           "logit_gap": logit_gap}
    if n > 1:
        out["change3_gap"] = norm_gap(norms.get(n), want["norms"][n],
                                      "change3")
    return out


def check(obj: Obj, cfg: dict, traffic: dict, seed: int,
          control: bool = False) -> list:
    """Frees the program's state, runs the reference over the check's
    rounds and returns [(name, value, limit)]."""
    import numpy as np
    n = obj.n_check
    losses = [rec.get("loss", math.nan) for rec in obj.eng.history[:n]]
    say = lambda *a: print("bench:", *a, file=sys.stderr)  # noqa: E731
    private = np.asarray(obj.task.x_clients["tokens"])
    open_ = np.asarray(obj.task.open_x["tokens"])
    obj.state = obj.eng = obj.task = None
    gc.collect()
    run = lambda precision: ref.run_rounds(  # noqa: E731
        cfg, obj.keys, private, open_, n, obj.devices, obj.mean_rows,
        obj.probe_rows, precision=precision)
    want = run("f32")
    say(f"reference losses {want['losses']}, program {losses}")
    say(f"round 1 teacher entropy {want['teacher_entropy']:.6f} nats, "
        f"clients' mean entropy {want['client_entropy']:.6f} nats")
    say(f"share of bf16 parameter elements the first update leaves "
        f"unchanged: {want['zero_update_share']:.6f}")
    got = compare(losses, obj.mean, obj.norms, obj.probe_top, want, n)
    if control:
        low = run("fp8")
        got.update({"control." + k: v for k, v in compare(
            low["losses"], low["mean"], low["norms"],
            low["probe"].argmax(-1), want, n).items()})
    lim = cfg["check"]["limits"]
    return [(k, v, lim[k.split(".")[-1]]) for k, v in got.items()]


# ------------------------------------------------------ counts (shapes) ----
def forward_flops_per_token(cfg: dict) -> float:
    """FLOPs of one token through the decoder and the head (2 per
    multiply-add): every weight matrix once, the tied head's d x V, and
    causal attention's two products over the (S + 1) / 2 positions a token
    attends on average at sequence length S."""
    L, h, hd, S = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                   cfg["head_dim"], cfg["seq_len"])
    return 2.0 * param_bytes(cfg) / 2.0 + L * 2 * 2 * h * hd * (S + 1) / 2


def round_flops(cfg: dict) -> float:
    """FLOPs one round requires over all clients, counted from shapes with
    no recompute: a training token (private and open batch) costs three
    forwards (the forward, and the backward's two products), a prediction
    token on the open batch one."""
    S = cfg["seq_len"]
    train = cfg["private_batch"] * S + cfg["open_batch"] * S
    infer = cfg["open_batch"] * S
    return float(cfg["clients"] * forward_flops_per_token(cfg)
                 * (3 * train + infer))
