"""Plain reference of a Qwen1.5 decoder (hf:Qwen/Qwen1.5-4B's architecture:
pre-norm RMSNorm, multi-head attention with QKV bias and rotary position
embedding on all head dims, SwiGLU MLP, a final RMSNorm, output head tied
to the token embedding) in straightforward `jax.numpy`.

Imports nothing of the system under test.  ``init_params`` makes the
random weights the benchmark serves, from a seed, in the parameter layout
the system reads (layers stacked on a leading axis).  ``logits_at`` runs
the full causal forward over whole sequences, one layer at a time so that
it fits, at float32 with HIGHEST matmul precision; ``gaps`` reads how far
a given token's logit lies below the best one.

The control (``precision="fp8"``) runs the same forward with every matmul
operand rounded to float8_e4m3 (per-row scales for activations,
per-output-column scales for weights, float32 accumulation): the
precision below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def init_params(key, c: dict, dtype=jnp.bfloat16) -> dict:
    """Random weights as the published config's ``initializer_range``
    prescribes for a fresh model: every matrix, the token embedding (also
    the output head) and the QKV biases N(0, initializer_range^2); norm
    scales one."""
    d, f, V, L = (c["hidden_size"], c["intermediate_size"], c["vocab_size"],
                  c["num_hidden_layers"])
    h, kh, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    std = float(c["initializer_range"])
    ks = jax.random.split(key, 10)

    def normal(k, shape):
        return (std * jax.random.normal(k, shape, F32)).astype(dtype)

    mix = {"wq": normal(ks[0], (L, d, h * hd)),
           "wk": normal(ks[1], (L, d, kh * hd)),
           "wv": normal(ks[2], (L, d, kh * hd)),
           "wo": normal(ks[3], (L, h * hd, d)),
           "bq": normal(ks[4], (L, h * hd)),
           "bk": normal(ks[5], (L, kh * hd)),
           "bv": normal(ks[6], (L, kh * hd))}
    ffn = {"w_gate": normal(ks[7], (L, d, f)),
           "w_up": normal(ks[8], (L, d, f)),
           "w_down": normal(jax.random.fold_in(ks[8], 1), (L, f, d))}
    ones = jnp.ones((L, d), F32)
    return {"embed": {"tok": normal(ks[9], (V, d))},
            "blocks": {"s0_n1": {"scale": ones}, "s0_mix": mix,
                       "s0_n2": {"scale": ones}, "s0_ffn": ffn},
            "final_norm": {"scale": jnp.ones((d,), F32)}}


# ----------------------------------------------------------- precision ----
def _fp8(x, axis):
    """Round to float8_e4m3 with a scale per slice along ``axis`` (the
    reduced dimension's max maps to the format's largest finite value)."""
    x = x.astype(F32)
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(F32)
    return q * scale


def _mm(x, w, precision: str):
    """x (..., k) @ w (k, n) at the reference's or the control's
    precision."""
    if precision == "fp8":
        x = _fp8(x, axis=-1)
        w = _fp8(w, axis=0)
    return jnp.matmul(x.astype(F32), w.astype(F32), precision=HIGHEST)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv                   # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


class Dims(NamedTuple):
    heads: int
    kv_heads: int
    head_dim: int
    eps: float
    theta: float


def dims(cfg: dict) -> Dims:
    return Dims(cfg["num_attention_heads"], cfg["num_key_value_heads"],
                cfg["head_dim"], float(cfg["rms_norm_eps"]),
                float(cfg["rope_theta"]))


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _layer(x, lp, c: Dims, precision):
    """One decoder layer over (B, S, d) float32 activations."""
    B, S, d = x.shape
    h, kh, hd, eps, theta = c
    pos = jnp.arange(S)
    a = _rms(x, lp["s0_n1"]["scale"], eps)
    m = lp["s0_mix"]
    q = (_mm(a, m["wq"], precision) + m["bq"].astype(F32)).reshape(B, S, h, hd)
    k = (_mm(a, m["wk"], precision) + m["bk"].astype(F32)).reshape(B, S, kh,
                                                                   hd)
    v = (_mm(a, m["wv"], precision) + m["bv"].astype(F32)).reshape(B, S, kh,
                                                                   hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    g = h // kh
    k = jnp.repeat(k, g, axis=2)
    v = jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v, precision=HIGHEST)
    x = x + _mm(o.reshape(B, S, h * hd), m["wo"], precision)
    a = _rms(x, lp["s0_n2"]["scale"], eps)
    f = lp["s0_ffn"]
    hmid = jax.nn.silu(_mm(a, f["w_gate"], precision)) \
        * _mm(a, f["w_up"], precision)
    return x + _mm(hmid, f["w_down"], precision)


@functools.partial(jax.jit, static_argnames=("c", "precision"))
def _head(hid, tok, scale, c, precision):
    """Final norm and tied output head over gathered rows (n, d)."""
    a = _rms(hid, scale, c.eps)
    return _mm(a, tok.T, precision)


def logits_at(params: dict, cfg: dict, seqs: list, rows: list,
              precision: str = "f32", length: int | None = None
              ) -> np.ndarray:
    """Logits of the full causal forward over each sequence in ``seqs``
    (lists of token ids) at the positions in ``rows`` (one list per
    sequence: the positions whose next-token logits are wanted), one layer
    at a time in float32.  Each sequence runs alone, right-padded to
    ``length`` (default the longest; causal attention never reads the
    padding), so that every call has the same shapes and compiles once.  Returns an (n_rows, V) float32 array,
    in order."""
    c = dims(cfg)
    S = length or max(len(s) for s in seqs)
    tok = params["embed"]["tok"]
    xs = []
    for seq in seqs:
        toks = np.zeros((1, S), np.int32)
        toks[0, :len(seq)] = seq
        xs.append(jnp.take(tok, jnp.asarray(toks), axis=0).astype(F32))
    for i in range(cfg["num_hidden_layers"]):    # one layer in f32 at a time
        lp = jax.tree.map(lambda a: a[i].astype(F32), params["blocks"])
        xs = [_layer(x, lp, c, precision) for x in xs]
        del lp
    hid = jnp.concatenate([x[0, jnp.asarray(np.asarray(r, np.int32))]
                           for x, r in zip(xs, rows)], axis=0)
    n, blk = hid.shape[0], 256
    hid = jnp.pad(hid, ((0, (-n) % blk), (0, 0)))
    out = [np.asarray(_head(hid[lo:lo + blk], tok,
                            params["final_norm"]["scale"], c, precision))
           for lo in range(0, hid.shape[0], blk)]
    return np.concatenate(out, axis=0)[:n]


def gaps(ref_logits: np.ndarray, tokens: np.ndarray) -> np.ndarray:
    """How far each token's reference logit lies below the best one."""
    best = ref_logits.max(axis=-1)
    got = np.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return best - got
