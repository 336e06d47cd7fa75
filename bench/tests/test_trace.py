"""The trace reduction, on a trace recorded on a v5e: five calls of the
DCGAN generator's deconv stack at batch 64, bf16 (four Pallas calls each)."""

import pathlib

import pytest

import trace as T

DATA = pathlib.Path(__file__).parent / "data" / "dcgan_serve_b64.xplane.pb"


@pytest.fixture(scope="module")
def tr():
    return T.load(str(DATA))


def test_planes_and_programs(tr):
    assert sorted(tr.devices) == [0]
    assert len(tr.modules[0]) == 5
    assert len(tr.devices[0]) == 285


def test_kinds(tr):
    ops = tr.devices[0]
    kernels = [o for o in ops if o.kind == "kernel"]
    assert len(kernels) == 5 * 4          # four deconv layers per call
    assert all("custom-call(" in o.name for o in kernels)
    by = T.time_by_kind(ops)
    assert sum(by.values()) == pytest.approx(sum(o.dur_ns for o in ops))
    assert by["kernel"] / 1e6 == pytest.approx(5.980988, rel=1e-6)


def test_busy_is_the_union(tr):
    ops = tr.devices[0]
    busy = T.busy_ns(ops)
    assert busy <= sum(o.dur_ns for o in ops) + 1e-6
    assert busy / 1e6 == pytest.approx(7.696958, rel=1e-6)
    # busy inside the programs' own spans is all of it
    spans = [(m.start_ns, m.start_ns + m.dur_ns) for m in tr.modules[0]]
    assert T.overlap_ns(ops, spans) == pytest.approx(busy, rel=1e-9)


def test_union_and_gaps_on_made_up_intervals():
    assert T.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert T.union_ns([(0, 10), (5, 15), (20, 30)], lo=8, hi=25) == 12
    ops = [T.Op("%a = f32[] add()", 0, 10), T.Op("%b = f32[] add()", 30, 10)]
    spans = [T.Span("bench.step", 5, 45), T.Span("bench.submit", 12, 18)]
    gaps = T.idle_gaps(ops, spans, 0, 50)
    assert gaps[0] == ["bench.step", 20e-9]      # 10..30: submit is over by 20
    assert gaps[1] == ["bench.step", 10e-9]      # 40..50


def test_top_ops(tr):
    top = T.top_ops(tr.devices[0], 3)
    assert len(top) == 3
    assert top[0][1] >= top[1][1] >= top[2][1]
    assert "custom-call" in top[0][0]


def test_overlap_is_busy_inside_the_windows():
    ops = [T.Op("%a = f32[] add()", 0, 10), T.Op("%b = f32[] add()", 5, 10),
           T.Op("%c = f32[] add()", 30, 10)]
    assert T.overlap_ns(ops, [(2, 8), (12, 35), (100, 110)]) == 6 + 3 + 5
    assert T.overlap_ns(ops, [(0, 50)]) == T.busy_ns(ops)


def test_host_spans_move_onto_the_trace_clock(tr):
    start = 1792178447897117358          # the trace's profile_start_time
    moved = T.load(str(DATA), [("bench.step", start + 1000, start + 5000)])
    assert [(s.name, s.start_ns, s.end_ns) for s in moved.spans] == \
        [("bench.step", 1000, 5000)]
    log = T.HostSpans()
    with log("bench.submit"):
        pass
    assert log.kept == []                # kept only while on
    log.on = True
    with log("bench.submit"):
        pass
    assert [k[0] for k in log.kept] == ["bench.submit"]
