"""Reduction of a profiler trace (``.xplane.pb``) to the device numbers the
per-layer metrics read.

What the v5e trace holds (read by hand from a chip run, see
``tests/fixtures/v5e_small.xplane.pb``): one plane per chip named
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed HLO
instruction, named by the instruction's text (``%fusion.3 = bf16[...]
fusion(...)``); a ``while`` event spans the events of its body.  Host
annotations (`jax.profiler.TraceAnnotation`) sit on the ``/host:CPU``
plane.  Both carry nanosecond times on one timeline; on the v5e the device
events run about a millisecond ahead of the host events that caused them,
which is far below the gaps this reduction attributes.

The reduction gives, for a window ``[t0, t1]`` of the host clock:

- busy time: the union of the device op intervals, per chip, averaged over
  chips; the idle share is one minus busy over the window;
- per-op device time, summed by instruction name (loop containers left
  out, their bodies counted);
- kernel time (Pallas ``tpu_custom_call`` instructions), by name;
- collective time, and the part of it during which no other op ran on
  that chip (exposed);
- per compiled program (``XLA Modules`` line): executions and device
  seconds, by the program's name without its hash;
- the longest idle gaps, each named by the innermost ``bench.*`` host span
  that covers its middle.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")
CONTAINERS = ("while", "conditional", "call")
HOST_PREFIX = "bench."

_INSTR = re.compile(r"^%(\S+) = ")
_OPCODE = re.compile(r" ([a-z][a-z0-9\-]*)\(")


@dataclass(frozen=True)
class DeviceOp:
    name: str        # HLO instruction name, e.g. "fusion.3"
    opcode: str      # e.g. "fusion", "custom-call", "all-reduce-start"
    kernel: bool     # a Pallas kernel (tpu_custom_call)
    start: float     # ns
    end: float       # ns

    @property
    def collective(self) -> bool:
        return self.opcode.startswith(COLLECTIVES)

    @property
    def container(self) -> bool:
        return self.opcode in CONTAINERS


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)     # device plane -> [DeviceOp]
    modules: dict = field(default_factory=dict)  # plane -> [(name, s, e)]
    spans: list = field(default_factory=list)   # (name, start, end), host


def parse_op(text: str, start: float, dur: float) -> DeviceOp:
    """One ``XLA Ops`` event -> DeviceOp (instruction name and opcode read
    from the instruction text; a bare name is kept as both)."""
    m = _INSTR.match(text)
    name = m.group(1) if m else text.split(" ", 1)[0].lstrip("%")
    rest = text[m.end():] if m else text
    c = _OPCODE.search(" " + rest)
    opcode = c.group(1) if c else name.split(".", 1)[0]
    kernel = opcode == "custom-call" and "tpu_custom_call" in text
    return DeviceOp(name, opcode, kernel, float(start), float(start + dur))


def load(path: str) -> Trace:
    """Read a ``.xplane.pb`` with `jax.profiler.ProfileData`."""
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(path))


def from_profile(pd) -> Trace:
    tr = Trace()
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend(parse_op(ev.name, ev.start_ns, ev.duration_ns)
                               for ev in line.events)
                elif line.name == "XLA Modules":
                    mods.extend((ev.name.split("(", 1)[0], float(ev.start_ns),
                                 float(ev.start_ns + ev.duration_ns))
                                for ev in line.events)
            if mods:
                tr.modules[plane.name] = mods
            if ops:
                tr.ops[plane.name] = sorted(ops, key=lambda o: o.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        tr.spans.append((ev.name, float(ev.start_ns),
                                         float(ev.start_ns + ev.duration_ns)))
    return tr


# ------------------------------------------------------------ intervals ----
def merge(intervals) -> list:
    """Union of (start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, t0: float, t1: float) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def subtract(a, b) -> list:
    """Parts of merged intervals ``a`` not covered by merged ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, t0: float, t1: float) -> list:
    """Idle intervals of the window: its complement of ``busy``."""
    return subtract([(t0, t1)], busy)


def host_span_at(spans, t: float) -> str:
    """Name of the innermost (shortest) host span that covers time t."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0] if best else "no bench span"


# -------------------------------------------------------------- reduce ----
def window_of(tr: Trace, name: str = "bench.window"):
    """(t0, t1) of the first host span called ``name``."""
    for n, s, e in tr.spans:
        if n == name:
            return s, e
    raise ValueError(f"trace holds no host span {name!r}")


def reduce(tr: Trace, t0: float, t1: float, top: int = 10) -> dict:
    """Device numbers of the window [t0, t1] (ns).  Times in seconds;
    busy, kernel and collective times are averaged over the chips."""
    n = max(len(tr.ops), 1)
    busy_s = coll_s = exposed_s = 0.0
    per_op, kernels, kernel_n = {}, {}, {}
    idle = []
    for i, (plane, ops) in enumerate(sorted(tr.ops.items())):
        ops = [o for o in ops if o.end > t0 and o.start < t1]
        busy = merge(clip([(o.start, o.end) for o in ops], t0, t1))
        busy_s += length(busy) / 1e9
        coll = merge(clip([(o.start, o.end) for o in ops if o.collective],
                          t0, t1))
        other = merge(clip([(o.start, o.end) for o in ops
                            if not o.collective and not o.container],
                           t0, t1))
        coll_s += length(coll) / 1e9
        exposed_s += length(subtract(coll, other)) / 1e9
        for o in ops:
            if o.container:
                continue
            d = (min(o.end, t1) - max(o.start, t0)) / 1e9
            per_op[o.name] = per_op.get(o.name, 0.0) + d
            if o.kernel:
                kernels[o.name] = kernels.get(o.name, 0.0) + d
                kernel_n[o.name] = kernel_n.get(o.name, 0) + 1
        if i == 0:      # gaps are attributed on the first chip
            idle = [(host_span_at(tr.spans, (s + e) / 2), (e - s) / 1e9)
                    for s, e in gaps(busy, t0, t1)]
    modules = {}
    for mods in tr.modules.values():
        for name, s, e in mods:
            if e > t0 and s < t1:
                c, d = modules.get(name, (0, 0.0))
                modules[name] = (c + 1, d + (min(e, t1) - max(s, t0)) / 1e9)
    window_s = (t1 - t0) / 1e9
    busy_s /= n
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
        "per_op_s": {k: v / n for k, v in per_op.items()},
        "kernel_s": {k: v / n for k, v in kernels.items()},
        "kernel_calls": {k: c / n for k, c in kernel_n.items()},
        "collective_s": coll_s / n,
        "exposed_collective_s": exposed_s / n,
        "modules": {k: (c / n, d / n) for k, (c, d) in modules.items()},
        "n_devices": len(tr.ops),
        "device_ops": sorted(([k, v / n] for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle),
                            key=lambda kv: -kv[1])[:top],
    }


def op_time(red: dict, predicate) -> float:
    """Device seconds (per chip) of the ops whose name satisfies
    ``predicate``."""
    return sum(v for k, v in red["per_op_s"].items() if predicate(k))
