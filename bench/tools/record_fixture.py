"""Record a small profiler trace on the chip for the trace-reduction tests.

    python bench/tools/record_fixture.py OUT_DIR

Runs a matmul, a scanned loop of small ops and a Pallas kernel under
`jax.profiler.trace`, each call inside a `bench.*` host annotation, with
idle gaps between them.  Copies the `.xplane.pb` to
``OUT_DIR/fixture.xplane.pb`` and prints every plane and line with its
event count and a few events, so the trace's layout can be read by hand.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile
import time


def main(out_dir: str) -> int:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind} x {len(jax.devices())}")
    if dev.platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2

    def add_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + y_ref[...]

    kern = jax.jit(lambda x, y: pl.pallas_call(
        add_kernel, out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name="fixture_add")(x, y))
    mm = jax.jit(lambda a, b: a @ b)
    loop = jax.jit(lambda x: jax.lax.scan(
        lambda c, _: (jnp.tanh(c) * 1.01, None), x, None, length=8)[0])
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    x = jnp.ones((512, 1024), jnp.float32)
    for f, args in ((mm, (a, a)), (loop, (x,)), (kern, (x, x))):
        jax.block_until_ready(f(*args))
    os.makedirs(out_dir, exist_ok=True)
    tmp = tempfile.mkdtemp(dir=out_dir)
    with jax.profiler.trace(tmp):
        for name, f, args in (("bench.matmul", mm, (a, a)),
                              ("bench.loop", loop, (x,)),
                              ("bench.kernel", kern, (x, x))):
            with jax.profiler.TraceAnnotation(name):
                jax.block_until_ready(f(*args))
            with jax.profiler.TraceAnnotation("bench.host_sleep"):
                time.sleep(0.002)
    (src,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
    dst = os.path.join(out_dir, "fixture.xplane.pb")
    shutil.copyfile(src, dst)
    shutil.rmtree(tmp)
    print(f"wrote {dst} ({os.path.getsize(dst)} bytes)")

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(dst)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: "
              + ", ".join(f"{ln.name!r}={sum(1 for _ in ln.events)}"
                          for ln in lines))
        for ln in lines:
            for i, ev in enumerate(ln.events):
                if i >= 6:
                    break
                stats = {k: v for k, v in ev.stats}
                print(f"   {ln.name!r}: {ev.name!r} start {ev.start_ns} "
                      f"dur {ev.duration_ns} stats {stats}")
    try:
        print("memory_stats", dev.memory_stats())
    except Exception as e:  # noqa: BLE001  (printed for the reader)
        print("memory_stats unavailable:", e)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1 else "."))
