"""Plain float32 reference of the LLM DS-FL round (`core.llm_dsfl` as
`LLMDSFLAlgorithm` runs it on `FedEngine`) over Qwen1.5 decoders, in
straightforward `jax.numpy` under ``jax.default_matmul_precision
("highest")``.

Imports nothing of the system under test.  The decoder layer, the final
norm with the tied head, and the initial weights are `reference.qwen`'s
(imported, unchanged).  One round, for each client k of K from its stored
bfloat16 parameters theta_k:

1. prediction: p_k = softmax(z_k) over the vocabulary at every token of
   the open batch;
2. ERA: the teacher t = softmax(mean_k p_k / T);
3. the client's loss: cross-entropy on its private batch (the label of
   position i is token i + 1, and the last position's is its own token,
   as the system's ``lm_loss`` has it) plus gamma times the distillation
   loss -sum_v t_v log softmax(z)_v on the open batch, each a mean over
   its tokens; the round's loss is the mean over clients;
4. plain SGD: theta_k <- bfloat16(float32(theta_k) - lr * grad), norm
   scales kept in float32, as the configuration stores the weights.

The open batch is the whole open set in its own order: the system draws
o_r as a permutation of it when |o_r| equals the set's size, and a
permutation moves nothing in the round but summation order.

How it fits a chip: client k lives on ``devices[k]``, as bfloat16 weights
held layer by layer; each layer is cast to float32 where it is used.  The
forward keeps each layer's float32 input; the loss and its gradient at the
head, and the predictions, are computed in blocks of tokens, so that no
(tokens x vocabulary) tensor is whole; the backward runs layer by layer in
reverse (``jax.vjp`` of a layer recomputes its forward) and applies each
layer's update as soon as its gradient is ready.  The ERA mean gathers the
clients' prediction blocks on the first device.

The departure from the published model is `reference.qwen`'s: the output
head is tied to the embedding (the configuration lists it under
``reduced``, with its reason).

The control (``precision="fp8"``) computes every matmul of the forward,
layers and head, with its operands rounded to float8_e4m3 as
`reference.qwen`'s control does, and takes the float32 gradient at the
same point (straight through the rounding).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from reference import qwen

F32 = jnp.float32


def _straight(low, high):
    """``low``'s value with ``high``'s gradient."""
    @jax.custom_vjp
    def f(*args):
        return low(*args)

    def fwd(*args):
        return low(*args), args

    def bwd(args, ct):
        return jax.vjp(high, *args)[1](ct)

    f.defvjp(fwd, bwd)
    return f


def _head_fn(c, precision):
    """The final norm and the tied head; the control's gradient is the
    float32 head's at the same point."""
    f32 = functools.partial(qwen._head, c=c, precision="f32")
    if precision == "f32":
        return f32
    return _straight(functools.partial(qwen._head, c=c, precision=precision),
                     f32)


# ------------------------------------------------------ jitted pieces ----
@functools.partial(jax.jit, static_argnames=("c",), donate_argnums=(2,))
def _layer_bwd(x, lp, dy, lr, c):
    """The layer's input gradient (float32, recomputing its forward) and
    its updated parameters, and how many of its bfloat16 elements the
    update left as they were.  The control's layers share it: their
    gradient is the float32 layer's at the same point."""
    lp32 = jax.tree.map(lambda a: a.astype(F32), lp)
    _, vjp = jax.vjp(functools.partial(qwen._layer, c=c, precision="f32"),
                     x, lp32)
    dx, g = vjp(dy)
    new = jax.tree.map(lambda p, p32, d: (p32 - lr * d).astype(p.dtype),
                       lp, lp32, g)
    same = sum(jnp.sum(n == p) for n, p in zip(jax.tree.leaves(new),
                                               jax.tree.leaves(lp))
               if p.dtype != F32)
    return dx, new, same


@jax.jit
def _embed(tok, tokens):
    return jnp.take(tok, tokens, axis=0).astype(F32)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _logits(h, tok, scale, c, precision):
    return _head_fn(c, precision)(h, tok.astype(F32), scale)


_softmax = jax.jit(functools.partial(jax.nn.softmax, axis=-1))


@functools.partial(jax.jit, static_argnums=(1,))
def _one_hot(labels, V):
    return jax.nn.one_hot(labels, V, dtype=F32)


@jax.jit
def _era(ps, temperature, rows, start, kept):
    """The teacher of a block of rows from the clients' (K, n, V)
    predictions, with the sums over rows of its entropy and of the
    clients' mean entropy; ``kept`` (len(rows), V) takes the clients' mean
    at those of the flat open-token ``rows`` that fall in this block,
    which starts at token ``start``."""
    mean = jnp.mean(ps, axis=0)
    t = jax.nn.softmax(mean / temperature, axis=-1)
    ent = jax.scipy.special.entr
    local = rows - start
    hit = (local >= 0) & (local < mean.shape[0])
    kept = jnp.where(hit[:, None],
                     mean[jnp.clip(local, 0, mean.shape[0] - 1)], kept)
    return (t, kept, jnp.sum(ent(t)),
            jnp.sum(jnp.mean(jnp.sum(ent(ps), axis=-1), axis=0)))


def _sq(a, b):
    d = a.astype(F32) - b.astype(F32)
    return jnp.sum(d * d)


def delta_norms(params, p0) -> dict:
    """The norm of each parameter's change from ``p0``, one per layer for
    the stacked blocks (``reference.qwen.init_params``'s layout, leaves
    (L, ...)): {leaf path: (L,) or ()}."""
    out = {}
    for path, a in jax.tree_util.tree_flatten_with_path(params)[0]:
        b = p0
        for k in path:
            b = b[k.key]
        name = jax.tree_util.keystr(path)
        if name.startswith("['blocks']"):
            out[name] = jnp.sqrt(jax.vmap(_sq)(a, b))
        else:
            out[name] = jnp.sqrt(_sq(a, b))
    return out


@functools.partial(jax.jit, static_argnames=("c", "precision"),
                   donate_argnums=(5, 6))
def _head_block(h, tok, scale, target, w, dtok, dscale, c, precision):
    """Loss of a block of rows against target distributions (``w`` times
    the sum over rows of -sum_v target_v log softmax(z)_v: cross-entropy
    for one-hot targets, distillation for the teacher's), its gradient at
    the hidden states, and the head's gradients added to ``dtok`` and
    ``dscale``."""
    head = _head_fn(c, precision)

    def loss(h, tok32, scale):
        z = head(h, tok32, scale)
        return w * jnp.sum(jax.nn.logsumexp(z, axis=-1)
                           - jnp.sum(target * z, axis=-1))

    val, vjp = jax.vjp(loss, h, tok.astype(F32), scale)
    dh, dt, ds = vjp(jnp.ones((), F32))
    return val, dh, dtok + dt, dscale + ds


@functools.partial(jax.jit, donate_argnums=(0,))
def _embed_update(tok, scale, tokens, dtok, dscale, dx, lr):
    d = tok.shape[-1]
    dtok = dtok.at[tokens.reshape(-1)].add(dx.reshape(-1, d))
    new = (tok.astype(F32) - lr * dtok).astype(tok.dtype)
    return new, scale - lr * dscale, jnp.sum(new == tok)


# ------------------------------------------------------------ clients ----
class Client:
    """One client's weights on its device, bfloat16 layer by layer."""

    def __init__(self, cfg: dict, init, key, device):
        self.device = device
        p = init(jax.device_put(key, device))
        blocks = p["blocks"]
        self.layers = [jax.tree.map(lambda a, i=i: a[i], blocks)
                       for i in range(cfg["num_hidden_layers"])]
        self.tok = p["embed"]["tok"]
        self.scale = p["final_norm"]["scale"]


def _blocks(n: int, blk: int):
    return [slice(lo, lo + blk) for lo in range(0, n, blk)]


def _forward(clients, toks, c, precision):
    """Every layer's float32 input per client, and the final hidden states
    flattened to (tokens, d); layer by layer over all clients, so that the
    devices run side by side."""
    acts = [[_embed(cl.tok, t)] for cl, t in zip(clients, toks)]
    for i in range(len(clients[0].layers)):
        for a, cl in zip(acts, clients):
            a.append(qwen._layer(a[-1], cl.layers[i], c, precision))
    hid = []
    for a in acts:
        h = a.pop()
        hid.append(h.reshape(-1, h.shape[-1]))
    return acts, hid


def run_rounds(cfg: dict, keys, private: np.ndarray, open_: np.ndarray,
               n_rounds: int, devices, mean_rows: np.ndarray,
               probe_rows: np.ndarray, precision: str = "f32",
               block: int = 512) -> dict:
    """``n_rounds`` rounds of the K clients whose initial weights are
    `reference.qwen.init_params` of ``keys[k]``, on ``private`` (K, B, S)
    and ``open_`` (B_o, S) tokens.  ``mean_rows`` and ``probe_rows`` are
    flat indices of open-batch tokens (sequence * S + position).

    Returns ``losses`` (the round losses), ``mean`` (round 1's mean of the
    clients' predictions, the aggregate ERA sharpens, at ``mean_rows``,
    (n, V)), ``probe`` (each client's logits at ``probe_rows`` after the
    last round, (K, n, V)), ``norms`` (after round 1 and after the last
    round: per client, `delta_norms` of its parameters from the initial
    ones), and round 1's ``teacher_entropy`` and ``client_entropy`` (mean
    over open tokens: the teacher's, and the mean over clients of each
    client's) and ``zero_update_share`` (the share of bfloat16 parameter
    elements the first update left as they were)."""
    with jax.default_matmul_precision("highest"):
        return _run(cfg, keys, private, open_, n_rounds, devices,
                    np.asarray(mean_rows), np.asarray(probe_rows),
                    precision, block)


def _teacher(clients, hid, n_p, n_o, blk, T, c, precision, rows):
    """ERA over the clients' predictions, a block of open tokens at a time:
    the teacher's blocks on each client's device, the clients' mean at
    the open tokens ``rows`` (on the first device), and the sums over open
    tokens of the teacher's entropy and of the clients' mean entropy."""
    dev0 = clients[0].device
    V = clients[0].tok.shape[0]
    blocks = [[] for _ in clients]
    ent_t = ent_c = 0.0
    rows = jax.device_put(np.asarray(rows, np.int32), dev0)
    kept = jnp.zeros((rows.shape[0], V), F32, device=dev0)
    for sl in _blocks(n_o, blk):
        osl = slice(n_p + sl.start, n_p + sl.stop)
        ps = [jax.device_put(_softmax(_logits(h[osl], cl.tok, cl.scale, c,
                                              precision)), dev0)
              for h, cl in zip(hid, clients)]
        t, kept, et, ec = _era(jnp.stack(ps), T, rows, sl.start, kept)
        del ps
        for b, cl in zip(blocks, clients):
            b.append(jax.device_put(t, cl.device))
        ent_t, ent_c = ent_t + et, ent_c + ec
        # one block in flight: the host would otherwise queue every
        # block's (K, n, V) stack on the first device at once
        jax.block_until_ready([b[-1] for b in blocks])
    return blocks, kept, ent_t, ent_c


def _client_step(clients, acts, hid, toks, labels, teacher, n_p, n_o, blk,
                 gamma, lr, c, precision):
    """Each client's loss at the head, a block of tokens at a time, then
    its backward a layer at a time, each layer's update applied at once.
    ``teacher`` holds each client's teacher blocks (consumed).  Returns
    the clients' losses and how many bfloat16 elements each update left
    as they were."""
    V, d = clients[0].tok.shape
    K = len(clients)
    loss = [jnp.zeros((), F32, device=cl.device) for cl in clients]
    dtok = [jnp.zeros((V, d), F32, device=cl.device) for cl in clients]
    dscale = [jnp.zeros((d,), F32, device=cl.device) for cl in clients]
    dh = [[] for _ in clients]
    parts = [(sl, None, 1.0 / n_p) for sl in _blocks(n_p, blk)]
    parts += [(slice(n_p + sl.start, n_p + sl.stop), b, gamma / n_o)
              for b, sl in enumerate(_blocks(n_o, blk))]
    for sl, b, w in parts:
        for k, cl in enumerate(clients):
            if b is None:
                target = _one_hot(labels[k][sl], V)
            else:
                target, teacher[k][b] = teacher[k][b], None
            val, g, dtok[k], dscale[k] = _head_block(
                hid[k][sl], cl.tok, cl.scale, target, w, dtok[k], dscale[k],
                c, precision)
            loss[k] = loss[k] + val
            dh[k].append(g)
        jax.block_until_ready(dtok)     # one block's targets at a time
    dy = [jnp.concatenate(g, axis=0).reshape(acts[k][0].shape)
          for k, g in enumerate(dh)]
    del dh
    same = [0] * K
    for i in reversed(range(len(clients[0].layers))):
        for k, cl in enumerate(clients):
            dy[k], cl.layers[i], s = _layer_bwd(acts[k][i], cl.layers[i],
                                                dy[k], lr, c)
            acts[k][i] = None
            same[k] = same[k] + s
    for k, cl in enumerate(clients):
        cl.tok, cl.scale, s = _embed_update(cl.tok, cl.scale, toks[k],
                                            dtok[k], dscale[k], dy[k], lr)
        same[k] = same[k] + s
    return [float(x) for x in loss], [int(s) for s in same]


def _run(cfg, keys, private, open_, n_rounds, devices, mean_rows,
         probe_rows, precision, block):
    c = qwen.dims(cfg)
    K = len(keys)
    lr, gamma, T = (float(cfg["lr"]), float(cfg["gamma"]),
                    float(cfg["temperature"]))
    devs = [devices[k % len(devices)] for k in range(K)]
    dtype = jnp.dtype(cfg["dtype"])
    init = jax.jit(lambda k: qwen.init_params(k, cfg, dtype))

    @jax.jit
    def norms(key, layers, tok, scale):
        """The client's `delta_norms` from its initial weights, made again
        from its key inside the program."""
        params = {"embed": {"tok": tok},
                  "blocks": jax.tree.map(lambda *a: jnp.stack(a), *layers),
                  "final_norm": {"scale": scale}}
        return delta_norms(params, qwen.init_params(key, cfg, dtype))
    clients = [Client(cfg, init, keys[k], devs[k]) for k in range(K)]
    n_p, n_o = private[0].size, open_.size
    blk = math.gcd(block, n_p, n_o)
    toks = [jax.device_put(np.concatenate([private[k], open_]), devs[k])
            for k in range(K)]
    labels = [jax.device_put(np.concatenate(
        [private[k][:, 1:], private[k][:, -1:]], axis=1).reshape(-1),
        devs[k]) for k in range(K)]
    out = {"losses": [], "norms": {}}
    for r in range(n_rounds):
        acts, hid = _forward(clients, toks, c, precision)
        teacher, kept, ent_t, ent_c = _teacher(clients, hid, n_p, n_o, blk,
                                               T, c, precision, mean_rows)
        if r == 0:
            out["mean"] = np.asarray(kept)
            out["teacher_entropy"] = float(ent_t) / n_o
            out["client_entropy"] = float(ent_c) / n_o
        loss, same = _client_step(clients, acts, hid, toks, labels, teacher,
                                  n_p, n_o, blk, gamma, lr, c, precision)
        del acts, hid, teacher
        out["losses"].append(float(np.mean(loss)))
        if r == 0:
            total = sum(a.size for a in jax.tree.leaves(
                [clients[0].layers, clients[0].tok]) if a.dtype != F32)
            out["zero_update_share"] = sum(same) / (total * K)
        if r + 1 in (1, n_rounds):
            out["norms"][r + 1] = [jax.tree.map(np.asarray, norms(
                jax.device_put(keys[k], cl.device), cl.layers, cl.tok,
                cl.scale)) for k, cl in enumerate(clients)]
    # each client's logits at the probe rows after the last round
    _, hid = _forward(clients, toks, c, precision)
    n = len(probe_rows)
    rows = np.zeros(-(-n // blk) * blk, np.int32)    # the blocks' shape
    rows[:n] = n_p + probe_rows
    out["probe"] = np.stack([np.concatenate([np.asarray(_logits(
        h[jax.device_put(rows[sl], cl.device)], cl.tok, cl.scale, c,
        precision)) for sl in _blocks(len(rows), blk)])[:n]
        for h, cl in zip(hid, clients)])
    return out
