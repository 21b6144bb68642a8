"""The trace reduction, on a trace recorded on a v5e (``fixtures/``, made
by ``tools/record_fixture.py``) and on small hand-made traces."""
from __future__ import annotations

import os

import pytest

import tracing

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "v5e_small.xplane.pb")


@pytest.fixture(scope="module")
def fixture_trace():
    return tracing.load(FIXTURE)


def test_fixture_layout(fixture_trace):
    tr = fixture_trace
    assert list(tr.ops) == ["/device:TPU:0"]
    names = [n for n, _s, _e in tr.spans]
    for want in ("bench.matmul", "bench.loop", "bench.kernel"):
        assert want in names
    assert [m[0] for m in tr.modules["/device:TPU:0"]] == ["jit__lambda"] * 3


def test_fixture_busy_per_op_and_kernel(fixture_trace):
    tr = fixture_trace
    t0 = min(s for _n, s, _e in tr.spans) - 2e6
    t1 = max(e for _n, _s, e in tr.spans)
    red = tracing.reduce(tr, t0, t1)
    # union of the 17 op intervals, counted by hand from the trace:
    # matmul 13 + 103012 ns, loop 5 + 3111 + 21492 + 6 + 3221 ns,
    # kernel 9717 ns
    assert red["busy_s"] == pytest.approx(140577e-9, abs=1e-12)
    assert red["window_s"] == pytest.approx((t1 - t0) / 1e9)
    assert red["idle_share"] == pytest.approx(1 - 140577e-9 * 1e9 / (t1 - t0))
    # the while's body: eight tanh fusions, the while itself not counted
    assert red["per_op_s"]["tanh_multiply_fusion.2"] == pytest.approx(
        21441e-9, abs=1e-12)
    assert "while" not in red["per_op_s"]
    assert red["kernel_s"] == {"fixture_add.1": pytest.approx(9717e-9)}
    assert red["kernel_calls"] == {"fixture_add.1": 1}
    assert red["collective_s"] == 0.0
    assert red["modules"]["jit__lambda"][0] == 3
    assert len(red["device_ops"]) <= 10 and len(red["idle_gaps"]) <= 10


def test_fixture_gaps_named_by_host_span(fixture_trace):
    tr = fixture_trace
    t0 = dict((n, s) for n, s, _e in tr.spans)["bench.matmul"]
    t1 = max(e for _n, _s, e in tr.spans)
    red = tracing.reduce(tr, t0, t1)
    names = {n for n, _d in red["idle_gaps"]}
    assert "bench.host_sleep" in names
    assert sum(d for _n, d in red["idle_gaps"]) <= red["window_s"]


def _xspace(events, spans=()):
    """A text-proto XSpace: one TPU plane with ``events`` = [(text, start
    ns, duration ns)] on its ``XLA Ops`` line and host ``spans``."""
    from jax.profiler import ProfileData
    meta, evs = [], []
    for i, (text, s, d) in enumerate(events, 1):
        meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{text}" }} }}')
        evs.append(f"events {{ metadata_id: {i} offset_ps: {s * 1000} "
                   f"duration_ps: {d * 1000} }}")
    host_meta, host_evs = [], []
    for i, (name, s, d) in enumerate(spans, 1):
        host_meta.append(f'event_metadata {{ key: {i} value {{ id: {i} '
                         f'name: "{name}" }} }}')
        host_evs.append(f"events {{ metadata_id: {i} offset_ps: {s * 1000} "
                        f"duration_ps: {d * 1000} }}")
    txt = ('planes { id: 1 name: "/device:TPU:0" lines { id: 1 '
           'name: "XLA Ops" timestamp_ns: 0 ' + " ".join(evs) + " } "
           + " ".join(meta) + " } "
           'planes { id: 2 name: "/host:CPU" lines { id: 1 name: "python" '
           'timestamp_ns: 0 ' + " ".join(host_evs) + " } "
           + " ".join(host_meta) + " }")
    return tracing.from_profile(ProfileData.from_text_proto(txt))


def test_exposed_collective_is_the_part_with_no_compute_beside_it():
    fus = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    ar = ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %fusion.1), "
          "replica_groups={}")
    ag = ("%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) "
          "all-gather-start(f32[8]{0} %fusion.1), dimensions={0}")
    tr = _xspace([(fus, 0, 4000), (ar, 2000, 4000), (ag, 9000, 1000)],
                 [("bench.window", 0, 12000), ("bench.host", 6000, 3000)])
    red = tracing.reduce(tr, *tracing.window_of(tr))
    assert red["collective_s"] == pytest.approx(5000e-9)
    # all-reduce 2000..6000 overlaps the fusion until 4000; the
    # all-gather has nothing beside it
    assert red["exposed_collective_s"] == pytest.approx(3000e-9)
    assert red["busy_s"] == pytest.approx(7000e-9)
    gaps = dict((round(d * 1e9), n) for n, d in red["idle_gaps"])
    assert gaps[3000] == "bench.host" and gaps[2000] == "bench.window"


def test_opcode_and_kernel_parsing():
    op = tracing.parse_op(
        '%era.3 = f32[8,128]{1,0} custom-call(f32[100,8,128]{2,1,0} %p), '
        'custom_call_target="tpu_custom_call"', 5, 10)
    assert (op.name, op.opcode, op.kernel) == ("era.3", "custom-call", True)
    w = tracing.parse_op("%while = (s32[]{:T(128)}, f32[8]{0}) while((s32[]"
                         "{:T(128)}, f32[8]{0}) %t), body=%b", 0, 1)
    assert w.container and not w.collective
    assert tracing.merge([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tracing.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
