"""The DS-FL round split into its paper steps on the device trace.

The program wraps each step of the round in a `jax.named_scope`
(`repro.core.algorithms`, `repro.core.llm_dsfl`; the test-set eval in
`repro.core.protocol.make_eval_fn`).  The scope lands in every HLO op's
``op_name`` metadata, which the device trace keeps as the ``tf_op`` stat of
each ``XLA Ops`` event's metadata (read by `xplane`; `ProfileData` does
not expose it).  A fusion carries the path of its root op, so a fusion
that XLA built across two steps counts wholly to the step of its root.

With `repro.obs.annotate(True)` the program's own host spans
(``engine.round``, ``engine.sample``, ``engine.sync``, ``serve.replay``,
...) also land on the trace's ``/host:CPU`` plane, on the device ops'
timeline, so an idle gap of the device can be named by the program step
the host was in.

`load` reads a trace into the `tracing.Trace` that `tracing.load` gives
(the same ops, programs and ``bench.*`` spans) plus each op's scope and
the program's spans; `reduce` gives, for a window ``[t0, t1]``:

- ``scope_s``: device seconds by scope, over the same ops as
  ``tracing.reduce``'s ``per_op_s`` (loop containers left out), averaged
  over the chips, so the two sum alike;
- ``idle_by_span``: idle seconds of the first chip, summed by the
  innermost program span that covers each gap's middle (``"no program
  span"`` where none does).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

import tracing
import xplane

# the paper's round steps, then the test-set eval (scope names in the
# program; one op belongs to the first of these on its name-stack path)
PHASES = ("dsfl_update", "dsfl_predict", "dsfl_aggregate", "dsfl_distill",
          "dsfl_server_distill", "fed_eval")
UNSCOPED = "unscoped"
# obs span categories the program emits (`repro.obs.trace.CATEGORIES`)
PROGRAM_PREFIXES = ("engine.", "serve.", "wire.", "cohort.", "sim.",
                    "swap.", "queue.")
NO_SPAN = "no program span"

# a path segment that is a scope name, possibly inside transform wrappers:
# "dsfl_update", "jvp(dsfl_update)", "transpose(jvp(dsfl_update))"
_SEGMENT = re.compile(r"^(?:[\w.\-]+\()*([\w.\-]+)\)*$")


def scope_of(path: str, phases=PHASES) -> str:
    """The first of ``phases`` that is a whole segment of the name-stack
    ``path`` (a ``tf_op``, whose ``:op_type`` suffix is dropped), or
    ``"unscoped"``."""
    path = path.rsplit(":", 1)[0] if ":" in path else path
    for seg in path.split("/"):
        m = _SEGMENT.match(seg)
        if m and m.group(1) in phases:
            return m.group(1)
    return UNSCOPED


@dataclass
class ScopedTrace:
    trace: tracing.Trace                            # as `tracing.load`
    scopes: dict = field(default_factory=dict)      # plane -> [scope per op]
    program_spans: list = field(default_factory=list)   # (name, start, end)


def load(path: str, phases=PHASES) -> ScopedTrace:
    """Read a ``.xplane.pb``: `tracing`'s trace, each device op's scope
    (aligned with ``trace.ops[plane]``) and the program's host spans."""
    st = ScopedTrace(tracing.Trace())
    tr = st.trace
    for plane in xplane.read(path):
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops, mods, cache = [], [], {}
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                for ev in line.events:
                    name = plane.event_names.get(ev.metadata_id, "")
                    s, d = xplane.start_ns(line, ev), xplane.duration_ns(ev)
                    if line.name == "XLA Modules":
                        mods.append((name.split("(", 1)[0], float(s),
                                     float(s + d)))
                        continue
                    sc = cache.get(ev.metadata_id)
                    if sc is None:
                        tf_op = plane.metadata_stats(ev.metadata_id).get(
                            "tf_op") or ""
                        sc = cache[ev.metadata_id] = scope_of(tf_op, phases)
                    ops.append((tracing.parse_op(name, s, d), sc))
            if mods:
                tr.modules[plane.name] = mods
            if ops:
                ops.sort(key=lambda o: o[0].start)
                tr.ops[plane.name] = [o for o, _sc in ops]
                st.scopes[plane.name] = [sc for _o, sc in ops]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = plane.event_names.get(ev.metadata_id, "")
                    s = xplane.start_ns(line, ev)
                    span = (name, float(s), float(s + xplane.duration_ns(ev)))
                    if name.startswith(tracing.HOST_PREFIX):
                        tr.spans.append(span)
                    elif name.startswith(PROGRAM_PREFIXES):
                        st.program_spans.append(span)
    return st


def reduce(st: ScopedTrace, t0: float, t1: float) -> dict:
    """``scope_s`` and ``idle_by_span`` of the window [t0, t1] (ns), in
    seconds (see the module docstring)."""
    tr = st.trace
    n = max(len(tr.ops), 1)
    scope_s, idle = {}, {}
    for i, plane in enumerate(sorted(tr.ops)):
        ops = tr.ops[plane]
        for o, sc in zip(ops, st.scopes[plane]):
            if o.container or o.end <= t0 or o.start >= t1:
                continue
            d = (min(o.end, t1) - max(o.start, t0)) / 1e9
            scope_s[sc] = scope_s.get(sc, 0.0) + d
        if i == 0:      # gaps are attributed on the first chip
            busy = tracing.merge(tracing.clip(
                [(o.start, o.end) for o in ops if o.end > t0 and o.start < t1],
                t0, t1))
            for s, e in tracing.gaps(busy, t0, t1):
                name = tracing.host_span_at(st.program_spans, (s + e) / 2)
                if name == "no bench span":
                    name = NO_SPAN
                idle[name] = idle.get(name, 0.0) + (e - s) / 1e9
    return {"scope_s": {k: v / n for k, v in scope_s.items()},
            "idle_by_span": idle}
