"""Closed-loop training rounds: the general generator of round traffic.

Set-up builds the family's compiled round with its state from the seed and
drives it through the traffic's ``check_steps`` first calls, the window's
own call; the family keeps what its check needs from them.  The window
then calls the same object again and again until ``--seconds`` have
passed, finishing the call in flight, and ``round_s`` is the window's wall
time over the rounds it completed.  After the window the family frees the
program's state and compares the first steps with its plain reference.

Traffic keys: ``driver`` ("rounds"), ``rounds_per_call``, ``check_steps``,
``trace_seconds`` (how much of a traced run's window the profiler keeps).
"""
from __future__ import annotations

import time


def run(cell) -> dict:
    import jax
    fam = cell.family
    tr = cell.traffic
    obj = fam.setup(cell.cfg, tr, cell.seed31, cell.devices)
    for i in range(int(tr["check_steps"])):
        with jax.profiler.TraceAnnotation("bench.setup_call"):
            fam.step(obj)
        fam.record(obj, i + 1)
    setup_s = cell.since_start()
    c0 = cell.counter.snapshot()
    prof = cell.profiler()
    rounds = calls = 0
    traced = None           # (rounds, wall s) of the traced part
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        prof.start()
        while True:
            with jax.profiler.TraceAnnotation("bench.round_call"):
                fam.step(obj)
            calls += 1
            rounds += int(tr["rounds_per_call"])
            if prof.poll():
                traced = (rounds, prof.stopped_at - t0)
            if time.perf_counter() - t0 >= cell.seconds:
                break
        t1 = time.perf_counter()
        if prof.running:
            prof.stop()
            traced = (rounds, prof.stopped_at - t0)
    c1 = cell.counter.snapshot()
    from harness import memory_peak
    mem = memory_peak(cell.devices)
    failed = fam.failed_rounds(obj)
    checks = fam.check(obj, cell.cfg, tr, cell.seed31, control=cell.control)
    out = dict(
        attempted=rounds, failed=failed, memory_peak_bytes=mem,
        trace=prof.result,
        e2e={"setup_s": setup_s, "round_s": (t1 - t0) / rounds},
        counters={"rounds": rounds, "calls": calls,
                  "window_wall_s": t1 - t0,
                  "traced_rounds": traced[0] if traced else None,
                  "traced_wall_s": traced[1] if traced else None,
                  "compiles_in_window": c1[0] - c0[0],
                  "traces_in_window": c1[1] - c0[1],
                  "compile_s_total": cell.counter.compile_s,
                  "setup_s": setup_s},
        checks=checks)
    return out
