"""Record a small profiler trace on the chip for the tests of `phases`.

    python bench/tools/record_scoped_fixture.py OUT_DIR

Runs, under `jax.profiler.trace` and inside one ``bench.window`` host
annotation:

- a program whose ``lax.scan`` body takes a vmapped ``grad`` step: the
  loss sits under ``jax.named_scope("dsfl_update")`` (so its forward and
  backward ops carry ``jvp(dsfl_update)`` and ``transpose(jvp(...))``),
  the parameter step, a matrix product of its own, under
  ``jax.named_scope("dsfl_distill")``;
- one `repro.obs` span, ``engine.sync``, with ``obs.annotate(True)``,
  around a host sleep, so the device is idle inside it;
- an unscoped program.

Host sleeps open and close the window, since the device events run about
a millisecond ahead of the host events that caused them.

Copies the ``.xplane.pb`` to ``OUT_DIR/fixture.xplane.pb`` and prints
every device op with its duration, ``tf_op`` path and scope, and every
host span, so the tests' numbers can be counted by hand.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp

    from repro import obs

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} x {len(jax.devices())}")
    if dev.platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    def loss(w, x):
        with jax.named_scope("dsfl_update"):
            return jnp.sum(jnp.tanh(x @ w) ** 2)

    def step(w, x, m):
        g = jax.vmap(jax.grad(loss))(w, x)
        with jax.named_scope("dsfl_distill"):
            return jnp.tanh(w - 0.01 * (g @ m))

    scoped = jax.jit(lambda w, x, m: jax.lax.scan(
        lambda c, _: (step(c, x, m), None), w, None, length=3)[0])
    tail = jax.jit(lambda w: jnp.sin(w) * 2.0 + 1.0)
    w = jnp.ones((8, 256, 256), jnp.float32) * 0.01
    x = jnp.ones((8, 512, 256), jnp.float32)
    m = jnp.eye(256, dtype=jnp.float32) * 0.5
    jax.block_until_ready(tail(scoped(w, x, m)))
    os.makedirs(out_dir, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=out_dir)
    prev = obs.annotate(True)
    try:
        with jax.profiler.trace(tmp):
            with jax.profiler.TraceAnnotation("bench.window"):
                time.sleep(0.005)
                w2 = jax.block_until_ready(scoped(w, x, m))
                with obs.span("engine.sync", "engine"):
                    time.sleep(0.003)
                jax.block_until_ready(tail(w2))
                time.sleep(0.005)
    finally:
        obs.annotate(prev)
    (src,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    dst = os.path.join(out_dir, "fixture.xplane.pb")
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp)
    print(f"wrote {dst} ({os.path.getsize(dst)} bytes)")

    import phases
    import xplane
    for plane in xplane.read(dst):
        for line in plane.lines:
            if line.name not in ("XLA Ops", "XLA Modules") and not (
                    plane.name.startswith("/host:")):
                continue
            for ev in line.events:
                name = plane.event_names.get(ev.metadata_id, "")
                if plane.name.startswith("/host:") and not name.startswith(
                        ("bench.", "engine.")):
                    continue
                tf_op = plane.metadata_stats(ev.metadata_id).get("tf_op", "")
                print(f"{plane.name} | {line.name} | start "
                      f"{xplane.start_ns(line, ev)} dur "
                      f"{xplane.duration_ns(ev)} | {name.split(' = ')[0]} | "
                      f"{tf_op} | {phases.scope_of(tf_op or '')}")
    st = phases.load(dst)
    import tracing
    t0, t1 = tracing.window_of(st.trace, "bench.window")
    print("reduce", phases.reduce(st, t0, t1))
    print("per_op_s total", sum(tracing.reduce(st.trace, t0, t1)[
        "per_op_s"].values()))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "."))
