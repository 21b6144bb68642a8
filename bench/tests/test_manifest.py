"""BENCHMARK.json against the benchmark's contract, and the harness
finding a new cell and a new per-layer metric from new files and new
manifest entries alone."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
import textwrap

import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_manifest_keys_and_names():
    m = harness.load_manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert m["command"][:2] == ["python3", "bench/run.py"]
    assert 1 <= m["run_seconds"] <= 51
    names = set()
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(harness.ROOT, c["file"]))
        fam = harness.load_json(c["file"])["family"]
        assert os.path.exists(os.path.join(harness.BENCH, "families",
                                           fam + ".py"))
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        tr = harness.load_json(f"bench/traffic/{w['traffic']}.json")
        assert os.path.exists(os.path.join(harness.BENCH, "drivers",
                                           tr["driver"] + ".py"))
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert x["name"] not in names
        names.add(x["name"])
    for x in m["end_to_end"]:
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.25
    for x in m["per_layer"]:
        assert x["moves"] in e2e
        assert os.path.exists(os.path.join(harness.BENCH, "metrics",
                                           x["name"] + ".py"))
        for w in x.get("workloads", []):
            reported = e2e[x["moves"]].get("workloads")
            assert reported is None or w in reported
    for w in m["workloads"]:
        assert harness.cell_metrics(m, w["name"], True), w["name"]
        assert len(harness.cell_metrics(m, w["name"], False)) >= 2


TOY_FAMILY = '''
"""A toy family: one jitted matmul step, checked against numpy."""
import numpy as np


class Obj:
    pass


def setup(cfg, traffic, seed, devices):
    import jax
    import jax.numpy as jnp
    o = Obj()
    o.x = jax.random.normal(jax.random.PRNGKey(seed), (cfg["n"], cfg["n"]))
    o.f = jax.jit(lambda a: jnp.tanh(a @ a / cfg["n"]))
    o.y = o.f(o.x).block_until_ready()
    o.first = None
    return o


def step(o):
    o.y = o.f(o.x).block_until_ready()


def record(o, i):
    o.first = o.first if o.first is not None else np.asarray(o.y)


def failed_rounds(o):
    return 0


def check(o, cfg, traffic, seed, control=False):
    x = np.asarray(o.x, np.float64)
    want = np.tanh(x @ x / cfg["n"])
    return [("max_diff", float(np.abs(o.first - want).max()), 1e-2)]


def round_flops(cfg):
    return 2.0 * cfg["n"] ** 3
'''

TOY_METRIC = '''
def read(rec):
    return float(rec.counters["calls"])
'''


def test_new_cell_and_metric_from_new_files_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    m = harness.load_manifest()
    # the additions: a configuration file and its family, a traffic file,
    # a metric reader, and entries in the manifest
    (root / "bench" / "families" / "toy_matmul.py").write_text(TOY_FAMILY)
    (root / "bench" / "configs" / "toy-256.json").write_text(
        json.dumps({"name": "toy-256", "family": "toy_matmul", "n": 256}))
    (root / "bench" / "traffic" / "toy-loop.json").write_text(json.dumps(
        {"driver": "rounds", "rounds_per_call": 1, "check_steps": 1,
         "trace_seconds": 0.2}))
    (root / "bench" / "metrics" / "toy.calls.py").write_text(TOY_METRIC)
    m["configs"].append({"name": "toy-256", "source": "https://example.org",
                         "file": "bench/configs/toy-256.json",
                         "reduced": [], "why": "toy"})
    m["workloads"].append({"name": "toy-256.loop", "config": "toy-256",
                           "traffic": "toy-loop", "chips": 1, "why": "toy"})
    for e in m["end_to_end"]:
        if e["name"] == "round_s":
            e["workloads"].append("toy-256.loop")
    m["per_layer"].append({"name": "toy.calls", "unit": "calls",
                           "better": "higher", "source": "program_counter",
                           "layer": "toy", "moves": "round_s",
                           "workloads": ["toy-256.loop"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    code = textwrap.dedent(f"""
        import sys, time
        t = time.perf_counter()
        sys.path.insert(0, {str(root / 'bench')!r})
        sys.path.insert(0, {os.path.join(harness.ROOT, 'src')!r})
        import harness, peaks
        peaks.PEAKS['cpu'] = peaks.PEAKS['TPU v5 lite']   # test only
        sys.exit(harness.run_cell('toy-256.loop', 7, 0.5, {{trace}}, t,
                                  root={str(root)!r}, platform='cpu'))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for trace, want in ((False, {"setup_s", "round_s"}),
                        (True, {"toy.calls"})):
        out = subprocess.run([sys.executable, "-c", code.format(trace=trace)],
                             capture_output=True, text=True, env=env,
                             timeout=300)
        assert out.returncode == 0, out.stderr[-3000:]
        res = json.loads(out.stdout.strip().splitlines()[-1])
        assert res["correct"] is True
        assert set(res["metrics"]) == want, res
        assert list(res)[-1] == "checks"


def test_refuses_a_platform_without_the_chip():
    out = subprocess.run(
        [sys.executable, os.path.join(harness.BENCH, "run.py"), "--workload",
         "cnn-k100.era-round", "--seed", "3", "--seconds", "1", "--trace",
         "0"], capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=harness.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a tpu" in out.stderr
