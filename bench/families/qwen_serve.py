"""A Qwen1.5 decoder served by the system under test: `ServeEngine` with
single-request `insert_batch` prefill shots and fused decode chunks, greedy.

The benchmark makes the bf16 weights from the seed on the device in one
jitted call (`reference.qwen.init_params`), in the layout the system reads.

The check: once the window has closed and the server's state is freed, a
sample of the finished requests drawn from the seed (the longest among
them, then others until ``check_tokens`` served tokens) is run through the
plain float32 reference, each prompt with its served tokens; ``logit_gap``
is the widest gap by which a served token's reference logit lies below the
reference's best at its position.  It covers the prefill (the first served
token is predicted by the prefill shot) and every cached decode step after
it.  With ``control`` the same sample is also read under the control (the
reference in fp8), as ``control.logit_gap``.
"""
from __future__ import annotations

import gc
import os
import sys
from dataclasses import dataclass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from reference import qwen as ref  # noqa: E402


def model_config(cfg: dict):
    """The system's `ModelConfig` for the configuration file's sizes."""
    from repro.models.base import ModelConfig
    return ModelConfig(
        name=cfg["name"], arch_type="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        head_dim=cfg["head_dim"], qkv_bias=cfg["qkv_bias"], act="swiglu",
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=cfg["tie_word_embeddings"], dtype=cfg["dtype"])


@dataclass
class Obj:
    engine: object
    params: dict


def used_buckets(traffic: dict) -> list:
    """The prefill lengths this traffic's prompts can hit."""
    from repro.serve.queue import bucket_of
    bs = sorted(traffic["buckets"])
    lo, hi = traffic["prompt_len"]["min"], traffic["prompt_len"]["max"]
    return sorted({bucket_of(n, bs) for n in range(lo, hi + 1)})


def setup(cfg: dict, traffic: dict, seed: int, devices) -> Obj:
    import jax
    import jax.numpy as jnp
    from repro.serve.engine import ServeEngine
    from repro.serve.queue import Request

    params = jax.jit(lambda k: ref.init_params(k, cfg, jnp.dtype(cfg["dtype"]))
                     )(jax.random.PRNGKey(seed))
    engine = ServeEngine(model_config(cfg), params, slots=cfg["slots"],
                         seq_budget=cfg["seq_budget"],
                         buckets=traffic["buckets"])
    # warm every shape the window uses: one prefill shot per bucket the
    # traffic hits (at each batch class up to the prefill group), and the
    # decode chunk
    d = int(cfg["decode_chunk"])
    classes = sorted({engine.batch_class(m)
                      for m in range(1, int(traffic["prefill_group"]) + 1)})
    rid = -1
    for b in used_buckets(traffic):
        for c in classes:
            reqs = []
            for _ in range(c):
                reqs.append(Request(id=rid, tokens=(1,) * b,
                                    max_new_tokens=d + 1))
                rid -= 1
            engine.insert_batch(reqs)
            engine.step(0.0, decode_chunk=d)
            engine.reset()
    jax.block_until_ready(engine.cache)
    return Obj(engine, params)


def sample(served: list, seed: int, tokens: int) -> list:
    """The longest finished request, then others in an order drawn from
    the seed, until ``tokens`` served tokens are in the sample."""
    import numpy as np
    if not served:
        return []
    order = list(np.random.default_rng(seed).permutation(len(served)))
    longest = max(range(len(served)),
                  key=lambda i: len(served[i][0]) + len(served[i][1]))
    order.remove(longest)
    out, n = [], 0
    for i in [longest] + order:
        out.append(served[i])
        n += len(served[i][1])
        if n >= tokens:
            break
    return out


def gap_readings(params, cfg: dict, picked: list, control: bool) -> dict:
    """Widest gap of the served tokens under the reference; with
    ``control``, also the widest gap of the tokens the fp8 control puts
    first at the same positions."""
    import numpy as np
    seqs = [list(p) + list(t[:-1]) for p, t in picked]
    rows = [[len(p) - 1 + j for j in range(len(t))] for p, t in picked]
    served = np.concatenate([np.asarray(t, np.int64) for _, t in picked])
    S = cfg["seq_budget"]
    logits = ref.logits_at(params, cfg, seqs, rows, length=S)
    out = {"logit_gap": float(ref.gaps(logits, served).max())}
    if control:
        low = ref.logits_at(params, cfg, seqs, rows, precision="fp8",
                            length=S)
        out["control.logit_gap"] = float(
            ref.gaps(logits, low.argmax(axis=-1)).max())
    return out


def check(obj: Obj, cfg: dict, traffic: dict, seed: int, served: list,
          control: bool = False) -> list:
    """[(name, value, limit)]; frees the server's state first."""
    import math
    picked = sample(served, seed, int(traffic["check_tokens"]))
    params = obj.params
    obj.engine = None
    gc.collect()
    lim = cfg["check"]["limits"]
    if not picked:
        return [("logit_gap", math.inf, lim["logit_gap"])]
    got = gap_readings(params, cfg, picked, control)
    print(f"bench: check sample = {len(picked)} requests, "
          f"{sum(len(t) for _, t in picked)} served tokens", file=sys.stderr)
    return [(k, v, lim[k.split(".")[-1]]) for k, v in got.items()]


# ------------------------------------------------------ counts (shapes) ----
def param_bytes(cfg: dict) -> float:
    d, f, V, L = (cfg["hidden_size"], cfg["intermediate_size"],
                  cfg["vocab_size"], cfg["num_hidden_layers"])
    h, kh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    per_layer = d * (h + 2 * kh) * hd + h * hd * d + 3 * d * f
    return 2.0 * (L * per_layer + V * d)


def kv_bytes_per_token(cfg: dict) -> float:
    return 2.0 * 2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] \
        * cfg["head_dim"]


def decode_step_flops(cfg: dict, slots: int, ctx_tokens: float) -> float:
    """FLOPs of one decode step of ``slots`` lanes whose caches hold
    ``ctx_tokens`` positions in all (2 per multiply-add): every weight
    matrix once per lane, the output head included, and attention's two
    products over each lane's context."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    weights = param_bytes(cfg) / 2.0
    return 2.0 * slots * weights + 4.0 * L * h * hd * ctx_tokens


def decode_step_bytes(cfg: dict, ctx_tokens: float) -> float:
    """HBM bytes one decode step must read: every weight once, and the
    K/V of every cached position."""
    return param_bytes(cfg) + kv_bytes_per_token(cfg) * ctx_tokens


def prefill_flops(cfg: dict, n: int) -> float:
    """FLOPs of one n-token prefill (causal attention counted in full)."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    h, hd = cfg["num_attention_heads"], cfg["head_dim"]
    return 2.0 * n * param_bytes(cfg) / 2.0 + 4.0 * L * h * hd * n * n
