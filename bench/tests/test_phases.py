"""The split of device time by named scope and of idle time by program
span (`phases`, read through `xplane`), on a trace recorded on a v5e
(``fixtures/v5e_scoped.xplane.pb``, made by
``tools/record_scoped_fixture.py``) and on small hand-made traces."""
from __future__ import annotations

import os
import struct

import pytest

import phases
import tracing

HERE = os.path.join(os.path.dirname(__file__), "fixtures")
SCOPED = os.path.join(HERE, "v5e_scoped.xplane.pb")
SMALL = os.path.join(HERE, "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def scoped():
    return phases.load(SCOPED)


def _whole(st):
    """A window holding every event of the trace."""
    tr = st.trace
    evs = [(o.start, o.end) for ops in tr.ops.values() for o in ops]
    evs += [(s, e) for _n, s, e in tr.spans + st.program_spans]
    return min(s for s, _e in evs), max(e for _s, e in evs)


@pytest.mark.parametrize("path", [SMALL, SCOPED])
def test_load_gives_what_tracing_load_gives(path):
    """The existing reduction reads the same trace from either reader, so
    every existing metric comes out the same."""
    st, tr = phases.load(path), tracing.load(path)
    assert st.trace.ops == tr.ops
    assert st.trace.modules == tr.modules
    assert st.trace.spans == tr.spans
    t0, t1 = _whole(st)
    assert tracing.reduce(st.trace, t0, t1) == tracing.reduce(tr, t0, t1)


def test_fixture_scope_s_counted_by_hand(scoped):
    red = phases.reduce(scoped, *_whole(scoped))
    # the scan body ran three times: the forward of the vmapped grad,
    # `convolution_tanh_fusion.2` (2845 + 2846 + 2846 ns, path
    # ".../vmap(jvp(dsfl_update))/dot_general"), and the step fused with
    # the backward, `fusion.25` (3 x 4491 ns, ".../dsfl_distill/...")
    assert red["scope_s"]["dsfl_update"] == pytest.approx(8537e-9, abs=1e-12)
    assert red["scope_s"]["dsfl_distill"] == pytest.approx(13473e-9,
                                                           abs=1e-12)
    # copies, slices and converts XLA added without op metadata (12212 ns)
    # and the unscoped program's `multiply_add_fusion` (15038 ns); the
    # `while` container (22018 ns) is left out, as in per_op_s
    assert red["scope_s"]["unscoped"] == pytest.approx(27250e-9, abs=1e-12)
    assert set(red["scope_s"]) == {"dsfl_update", "dsfl_distill",
                                   "unscoped"}


def test_fixture_scopes_sum_to_per_op_time(scoped):
    t0, t1 = _whole(scoped)
    red = phases.reduce(scoped, t0, t1)
    per_op = tracing.reduce(scoped.trace, t0, t1)["per_op_s"]
    assert sum(red["scope_s"].values()) == pytest.approx(
        sum(per_op.values()), rel=1e-12)
    # and inside the host window, which opens before the device work
    t0, t1 = tracing.window_of(scoped.trace, "bench.window")
    red = phases.reduce(scoped, t0, t1)
    per_op = tracing.reduce(scoped.trace, t0, t1)["per_op_s"]
    assert sum(red["scope_s"].values()) == pytest.approx(
        sum(per_op.values()), rel=1e-12)


def test_fixture_idle_named_by_the_obs_span(scoped):
    assert [n for n, _s, _e in scoped.program_spans] == ["engine.sync"]
    red = phases.reduce(scoped, *tracing.window_of(scoped.trace,
                                                   "bench.window"))
    # the device sat idle from the end of the scoped program's last copy
    # (50,539,587 ns) to the unscoped program (54,837,668 ns): the host
    # was sleeping inside `engine.sync`
    assert red["idle_by_span"]["engine.sync"] == pytest.approx(
        4298081e-9, abs=1e-12)
    # before and after: the window's opening and closing sleeps
    assert red["idle_by_span"][phases.NO_SPAN] == pytest.approx(
        (4984981 + 7635287) * 1e-9, abs=1e-7)


@pytest.mark.parametrize("path,scope", [
    ("jit(round)/dsfl_update/while/body/conv:", "dsfl_update"),
    ("jit(f)/while/body/closed_call/vmap(jvp(dsfl_update))/dot_general:",
     "dsfl_update"),
    ("jit(f)/transpose(jvp(dsfl_distill))/mul:", "dsfl_distill"),
    ("jit(f)/dsfl_aggregate/era_sharpen/pallas_call:", "dsfl_aggregate"),
    ("jit(_logits)/fed_eval/conv_general_dilated:conv", "fed_eval"),
    ("jit(f)/dsfl_predict/jvp(dsfl_update)/x:", "dsfl_predict"),
    ("jit(f)/dsfl_updates/mul:", "unscoped"),
    ("jit(<lambda>)/while/body/closed_call/mul:", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(path, scope):
    assert phases.scope_of(path) == scope


# ------------------------------------------------- hand-made wire format ---
def _varint(v: int) -> bytes:
    out = b""
    while True:
        b, v = v & 0x7F, v >> 7
        if v:
            out += bytes([b | 0x80])
        else:
            return out + bytes([b])


def _field(num: int, v) -> bytes:
    if isinstance(v, int):
        return _varint(num << 3) + _varint(v)
    if isinstance(v, str):
        v = v.encode()
    return _varint(num << 3 | 2) + _varint(len(v)) + v


def _plane(name, lines, events, stat_names=()):
    """An XPlane: ``lines`` = [(line name, [(metadata id, start ns, dur
    ns)])], ``events`` = {metadata id: (name, tf_op or None)}."""
    body = _field(2, name)
    for lname, evs in lines:
        line = _field(2, lname) + _field(3, 0)
        for mid, s, d in evs:
            line += _field(4, _field(1, mid) + _field(2, s * 1000)
                           + _field(3, d * 1000))
        body += _field(3, line)
    for mid, (ename, tf_op) in events.items():
        meta = _field(1, mid) + _field(2, ename)
        if tf_op is not None:
            # a reference stat, as the TPU writes it: the value is the
            # name of another stat metadata entry
            ref = 100 + mid
            meta += _field(5, _field(1, 1) + _field(7, ref))
            body += _field(5, _field(1, ref) + _field(2, _field(1, ref)
                                                      + _field(2, tf_op)))
        body += _field(4, _field(1, mid) + _field(2, meta))
    body += _field(5, _field(1, 1) + _field(2, _field(1, 1)
                                            + _field(2, "tf_op")))
    return _field(1, body)


def test_hand_made_trace(tmp_path):
    """Two device ops in two scopes and one without metadata; idle gaps
    inside nested program spans go to the innermost, outside any to "no
    program span"; bench spans stay where `tracing` keeps them."""
    fus = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    cp = "%copy.2 = f32[8]{0} copy(f32[8]{0} %fusion.1)"
    dev = _plane("/device:TPU:0",
                 [("XLA Ops", [(1, 1000, 2000), (2, 5000, 1000),
                               (3, 9000, 500)])],
                 {1: (fus, "jit(r)/jvp(dsfl_update)/add:"),
                  2: (fus.replace(".1", ".4"), "jit(r)/dsfl_distill/mul:"),
                  3: (cp, None)})
    host = _plane("/host:CPU",
                  [("python", [(1, 0, 12000), (2, 2500, 9000),
                               (3, 3500, 1000), (4, 7000, 1000)])],
                  {1: ("bench.window", None), 2: ("engine.round", None),
                   3: ("engine.sync", None), 4: ("other.span", None)})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(dev + host)
    st = phases.load(str(path))
    assert [n for n, _s, _e in st.trace.spans] == ["bench.window"]
    assert [n for n, _s, _e in st.program_spans] == ["engine.round",
                                                     "engine.sync"]
    red = phases.reduce(st, *tracing.window_of(st.trace, "bench.window"))
    assert red["scope_s"] == {"dsfl_update": pytest.approx(2e-6),
                              "dsfl_distill": pytest.approx(1e-6),
                              "unscoped": pytest.approx(0.5e-6)}
    # gaps: 0-1000 (none), 3000-5000 (mid 4000: engine.sync), 6000-9000
    # (mid 7500: engine.round; other.span is not a program span),
    # 9500-12000 (mid 10750: engine.round)
    assert red["idle_by_span"] == {
        phases.NO_SPAN: pytest.approx(1e-6),
        "engine.sync": pytest.approx(2e-6),
        "engine.round": pytest.approx(5.5e-6)}
    # a fusion's scope is its root's: its own name is only in per_op_s
    red0 = tracing.reduce(st.trace, 0, 12000)
    assert sum(red0["per_op_s"].values()) == pytest.approx(
        sum(red["scope_s"].values()))


def test_xplane_reads_doubles_and_signed():
    import xplane
    stat = (_field(1, 1) + _varint(2 << 3 | 1)
            + struct.pack("<d", 2.5))
    plane = xplane.Plane(stat_names={1: "flops"})
    assert plane.stats([stat]) == {"flops": 2.5}
    neg = _field(1, 1) + _field(4, (1 << 64) - 3)
    assert plane.stats([neg]) == {"flops": -3}
