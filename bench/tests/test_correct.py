"""What decides ``correct``, driven through the harness on the CPU at
sizes a test run can hold: sound runs pass, the control fails, and each
fault a cell can have (the timed path broken underneath the harness)
turns ``correct`` false.  The harness's look for a chip is skipped
(``platform="cpu"``); everything else is a whole run."""
from __future__ import annotations

import json
import os
import shutil
import time

import pytest

import faults
import harness
import peaks

TINY = {
    # at this size (f32 on the CPU) sound runs read ~1e-5 and the bf16
    # control ~1e-2 to 1e-1, so the limits are cut with the sizes
    "mnist-cnn-k100": dict(clients=4, n_private=400, n_open=400, n_test=200,
                           open_batch=100, local_epochs=1, distill_epochs=1,
                           batch_size=50,
                           check={"limits": {"loss_gap": 0.01,
                                             "update1_gap": 0.01,
                                             "change3_gap": 0.01}}),
    "qwen1.5-4b": dict(hidden_size=128, intermediate_size=256,
                       num_hidden_layers=2, num_attention_heads=4,
                       num_key_value_heads=4, head_dim=32, vocab_size=512,
                       slots=4, seq_budget=96, decode_chunk=4,
                       # at this width the published 0.02 would leave the
                       # residual stream to the token's own embedding, and
                       # with the head tied to it the model would copy its
                       # input whatever the context: no check could fail
                       initializer_range=0.2),
}
TINY_TRAFFIC = {
    "serve-poisson": dict(rate_per_s=4.0, buckets=[8, 16, 32, 48],
                          check_tokens=60,
                          prompt_len=dict(dist="lognormal", median=20,
                                          sigma=0.8, min=8, max=64),
                          output_len=dict(dist="lognormal", median=8,
                                          sigma=0.7, min=4, max=24)),
}
CNN = "cnn-k100.era-round"
SERVE = "qwen1.5-4b.serve-poisson"
# the serving cell's entries, for a manifest that does not admit it yet:
# its harness (driver, family, reference, control) is tested all the same
_SRV = [SERVE]
SERVE_ENTRIES = {
    "configs": [{"name": "qwen1.5-4b",
                 "source": "https://huggingface.co/Qwen/Qwen1.5-4B",
                 "file": "bench/configs/qwen1.5-4b.json",
                 "reduced": ["tie_word_embeddings", "rms_norm_eps"],
                 "why": "served model"}],
    "workloads": [{"name": SERVE, "config": "qwen1.5-4b",
                   "traffic": "serve-poisson", "chips": 1,
                   "why": "open-loop chat traffic"}],
    "end_to_end": [
        {"name": n, "unit": u, "better": b, "bound": 0.25,
         "source": "host_clock", "workloads": _SRV}
        for n, u, b in (("ttft_p90_ms", "ms", "lower"),
                        ("tpot_p90_ms", "ms", "lower"),
                        ("serve_tok_s", "tokens/s", "higher"))],
    "per_layer": [],
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose configurations and traffic are cut to test size;
    the code and the limits are the benchmark's own."""
    r = tmp_path_factory.mktemp("checkout")
    shutil.copytree(harness.BENCH, r / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    m = harness.load_manifest()
    for kind, entries in SERVE_ENTRIES.items():
        names = {e["name"] for e in m[kind]}
        m[kind] += [e for e in entries if e["name"] not in names]
    (r / "BENCHMARK.json").write_text(json.dumps(m))
    for name, cut in TINY.items():
        p = r / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(p.read_text())
        cfg.update(cut)
        p.write_text(json.dumps(cfg))
    for name, cut in TINY_TRAFFIC.items():
        p = r / "bench" / "traffic" / f"{name}.json"
        tr = json.loads(p.read_text())
        tr.update(cut)
        p.write_text(json.dumps(tr))
    return r


@pytest.fixture(autouse=True)
def cpu_peaks(monkeypatch):
    # the CPU has no row in the peak table; the per-layer numbers of these
    # runs are not read
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def run(root, capsys, cell, seed=20260417, seconds=1.0):
    rc = harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                          root=str(root), platform="cpu")
    assert rc == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(line)


def run_control(root, capsys, cell, seed=11, seconds=1.0):
    rc = harness.run_cell(cell, seed, seconds, False, time.perf_counter(),
                          root=str(root), platform="cpu", control=True)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


def control_fails(res) -> bool:
    """The control's run is not correct, and that is the control's doing:
    one of its numbers is over its limit."""
    over = [k for k, c in res["checks"].items()
            if k.startswith("control.") and c["value"] > c["limit"]]
    return res["correct"] is False and bool(over)


# ------------------------------------------------------------ training ----
def test_cnn_sound_run_is_correct(root, capsys):
    res = run(root, capsys, CNN)
    assert res["correct"] is True, res["checks"]
    assert set(res["checks"]) == {"loss_gap", "update1_gap", "change3_gap"}


def test_cnn_control_is_not_correct(root, capsys):
    res = run_control(root, capsys, CNN)
    assert control_fails(res), res["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_cnn_fault_is_not_correct(root, capsys, fault):
    with faults.FAULTS[fault]():
        res = run(root, capsys, CNN)
    assert res["correct"] is False, res["checks"]


# ------------------------------------------------------------- serving ----
def test_serve_sound_run_is_correct(root, capsys):
    res = run(root, capsys, SERVE, seconds=3.0)
    assert res["correct"] is True, res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 3


def test_serve_control_is_not_correct(root, capsys):
    res = run_control(root, capsys, SERVE, seconds=3.0)
    assert control_fails(res), res["checks"]


@pytest.mark.parametrize("fault", ["token_altered", "cache_unchanged"])
def test_serve_fault_is_not_correct(root, capsys, fault):
    with faults.FAULTS[fault]():
        res = run(root, capsys, SERVE, seconds=3.0)
    assert res["correct"] is False, res["checks"]
