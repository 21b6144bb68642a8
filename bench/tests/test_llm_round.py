"""The LLM DS-FL round's cell (``qwen1.5-4b-fl-k4.era-round``) driven
through the harness on the CPU at test size, K=4 clients on one device: a
sound run passes its check against the float32 reference, and the control
and each fault (the state left unchanged, the distillation term dropped,
half of each private batch left out, the exchange dropped) make
``correct`` false.  Also the round's FLOP count against a hand count.
"""
from __future__ import annotations

import json
import shutil
import time
from contextlib import contextmanager

import pytest

import faults
import harness
import peaks

CELL = "qwen1.5-4b-fl-k4.era-round"
CONFIG = "qwen1.5-4b-fl-k4"
TINY = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
            num_attention_heads=4, num_key_value_heads=4, head_dim=32,
            vocab_size=512, seq_len=32, private_batch=2, open_batch=2,
            # as test_correct.py's tiny qwen: at this width the published
            # 0.02 leaves the residual stream to the token's own embedding
            initializer_range=0.2)
# readings at this size (bf16 on the CPU, seed 20260417 unless named):
# sound loss 1.1e-4-5.0e-4, mean 0.022-0.033, update1 0.090-0.305 (a key
# or value bias of layer 0), change3 0.043-0.169, logits 0-0.162 (seeds
# 20260417, 11, 12); the fp8 control 1.7e-3, 0.225, 0.315, 0.185, 2.04;
# the exchange dropped: mean 0.681; half the batch: loss 0.0196, update1
# 1.03, change3 0.834; the distillation term dropped: loss 0.519; the
# state unchanged: update1 and change3 1.0, loss and mean missing
TINY_CHECK = dict(mean_positions=16, probe_positions=16,
                  limits={"loss_gap": 0.01, "mean_tv_gap": 0.08,
                          "update1_gap": 0.7, "change3_gap": 0.7,
                          "logit_gap": 0.5})


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose configuration is cut to test size and whose cell
    asks for the one CPU device; the code and the checks are the
    benchmark's own."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copytree(harness.BENCH, r / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    m = harness.load_manifest()
    harness.find(m["workloads"], CELL, "workload")["chips"] = 1
    (r / "BENCHMARK.json").write_text(json.dumps(m))
    p = r / "bench" / "configs" / f"{CONFIG}.json"
    cfg = json.loads(p.read_text())
    cfg.update(TINY)
    cfg["check"].update(TINY_CHECK)
    p.write_text(json.dumps(cfg))
    return r


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def run(root, capsys, control=False, seed=20260417):
    rc = harness.run_cell(CELL, seed, 1.0, False, time.perf_counter(),
                          root=str(root), platform="cpu", control=control)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@contextmanager
def kd_dropped():
    """The round's distillation term dropped: gamma = 0 in effect."""
    import repro.core.llm_dsfl as llm
    with faults._patched(llm, "distill_xent",
                         lambda logits, teacher, *a, **k: 0.0):
        yield


@contextmanager
def half_batch():
    """Half of each client's private sequences left out of its
    cross-entropy, the mean taken over the rest."""
    import repro.core.llm_dsfl as llm
    full = llm.xent_int_labels

    def half(logits, labels):
        n = logits.shape[0] // 2
        return full(logits[:n], labels[:n])

    with faults._patched(llm, "xent_int_labels", half):
        yield


@contextmanager
def exchange_dropped():
    """No exchange: client 0's own prediction in place of the clients'
    mean, as if the others' uploads never arrived."""
    import jax.numpy as jnp
    import repro.core.llm_dsfl as llm
    with faults._patched(llm, "client_mean",
                         lambda probs, weights: probs[0].astype(jnp.float32)):
        yield


FAULTS = {"state_unchanged": faults.state_unchanged,
          "kd_dropped": kd_dropped, "half_batch": half_batch,
          "exchange_dropped": exchange_dropped}


def test_llm_round_sound_run_is_correct(root, capsys):
    res = run(root, capsys)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"loss_gap", "mean_tv_gap", "update1_gap",
                                  "change3_gap", "logit_gap"}
    assert res["failed"] == 0 and res["attempted"] > 3


def test_llm_round_control_is_not_correct(root, capsys):
    res = run(root, capsys, control=True)
    over = [k for k, c in res["checks"].items()
            if k.startswith("control.") and c["value"] > c["limit"]]
    assert res["correct"] is False and over, res["checks"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_llm_round_fault_is_not_correct(root, capsys, fault):
    with FAULTS[fault]():
        res = run(root, capsys)
    assert res["correct"] is False, res["checks"]


def test_llm_round_flops_by_hand():
    fam = harness.part("families", "llm_dsfl")
    cfg = harness.load_json(f"bench/configs/{CONFIG}.json")
    cfg.update(num_hidden_layers=2, seq_len=8, private_batch=3,
               open_batch=2, clients=4)
    d, f, V, L, S = 2560, 6912, 151936, 2, 8
    weights = L * (d * 3 * d + d * d + 3 * d * f) + d * V
    # a token at position i attends over i + 1 positions: QK^T and PV,
    # 20 heads x 128, 2 FLOPs a multiply-add; 36 positions over 8 tokens
    attn = L * 2 * 2 * 20 * 128 * sum(i + 1 for i in range(S)) / S
    per_token = 2 * weights + attn
    assert fam.forward_flops_per_token(cfg) == pytest.approx(per_token,
                                                            rel=1e-12)
    # per client: 3 x 8 private and 2 x 8 open training tokens at three
    # forwards, 2 x 8 open prediction tokens at one
    want = 4 * per_token * (3 * (24 + 16) + 16)
    assert fam.round_flops(cfg) == pytest.approx(want, rel=1e-12)
    # the cell as configured: ~7.9e13 a client, one client a chip
    full = harness.load_json(f"bench/configs/{CONFIG}.json")
    assert fam.round_flops(full) / 4 == pytest.approx(7.86e13, rel=5e-3)
