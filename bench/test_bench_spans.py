"""The program's spans in a trace (``bench/spans.py``): the in-order join of
device programs to the spans that launched them, device-queue waits, self
times, span-named idle gaps and the readings made of them."""
import os
import sys
from types import SimpleNamespace as NS

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import spans as sp  # noqa: E402
from bench import trace as tr  # noqa: E402

LAUNCH = "PjitFunction(<lambda>)"
DISPATCHER, UPDATER, CLIENT = 5, 6, 7
MS = 1e6                        # ns


def _synthetic():
    """Window 0..1000 ms (times below in ms, the trace's are ns).  The dispatcher serves a 3-lane BFS delta group
    (padded to 4) and a 4-lane SSSP full group, then launches a last rung
    that the trace ends before the device starts; the updater commits in
    between.  The device runs the programs in launch order."""
    t = tr.Trace()
    host = [(CLIENT, 0, 1000, "bench.window"),
               (CLIENT, 0, 1000, "bench.client.wait"),
               (DISPATCHER, 180, 190, LAUNCH),
               (DISPATCHER, 181, 189, LAUNCH),     # nested twin event
               (DISPATCHER, 450, 460, LAUNCH),
               (DISPATCHER, 940, 945, LAUNCH),
               (UPDATER, 320, 340, "PjitFunction(apply_batch)")]
    t.host += [(th, s * MS, e * MS, n) for th, s, e, n in host]
    t.modules += [(s * MS, e * MS, n) for s, e, n in (
        (200, 400, "jit__lambda"), (400, 460, "jit_apply_batch"),
        (470, 770, "jit__lambda"))]
    t.ops.append([(s, e) for s, e, _ in t.modules])
    d = DISPATCHER
    spans = [
        sp.Span(d, 100, 300, "dispatch", {"kind": "bfs", "batch": 3}),
        sp.Span(d, 110, 150, "classify", {"lanes": 3}),
        sp.Span(d, 160, 200, "rung", {"kind": "bfs", "rung": "delta",
                                      "lanes": 3, "pad": 1}),
        sp.Span(d, 250, 290, "finish", {"lanes": 3}),
        sp.Span(d, 400, 600, "dispatch", {"kind": "sssp", "batch": 4}),
        sp.Span(d, 410, 420, "classify", {"lanes": 4}),
        sp.Span(d, 430, 470, "rung", {"kind": "sssp", "rung": "full",
                                      "lanes": 4, "pad": 0}),
        sp.Span(d, 500, 510, "finish", {"lanes": 4}),
        sp.Span(d, 900, 950, "rung", {"kind": "bfs", "rung": "full",
                                      "lanes": 1, "pad": 0}),
        sp.Span(UPDATER, 300, 700, "commit", {"batch_ops": 32}),
        sp.Span(UPDATER, 310, 350, "apply", {}),
        sp.Span(UPDATER, 360, 380, "ring_commit", {}),
    ]
    for x in spans:
        x.start, x.end = x.start * MS, x.end * MS
    return sp.Spans(t, spans)


def test_join_in_launch_order_drops_trailing_launches():
    st = _synthetic()
    joined = sp.join(st, "rung")
    assert [(j.span.stats["kind"], j.start, j.launch) for j in joined] \
        == [("bfs", 200 * MS, 180 * MS), ("sssp", 470 * MS, 450 * MS)]
    red = sp.reduce(st)
    assert red.rungs.keys() == {"bfs/delta", "sssp/full"}
    assert red.rungs["bfs/delta"] == pytest.approx(
        {"device_s": 0.2, "dispatches": 1, "lanes": 3, "pads": 1})
    assert red.rungs["sssp/full"] == pytest.approx(
        {"device_s": 0.3, "dispatches": 1, "lanes": 4, "pads": 0})
    # the join accounts for every rung program the window holds
    programs = tr.reduce(st.base).programs
    assert sum(r["device_s"] for r in red.rungs.values()) == \
        pytest.approx(programs["jit__lambda"])
    assert red.rungs_joined == 2
    # within the clocks' alignment a program may read as starting first
    st.base.modules[0] = (179.2 * MS, 400 * MS, "jit__lambda")
    assert sp.join(st, "rung")[0].start - 180 * MS == pytest.approx(-0.8e6)


@pytest.mark.parametrize("fault", ["extra_program", "span_launched_nothing",
                                   "program_before_launch"])
def test_mid_trace_mismatch_leaves_the_join_unmade(fault):
    st = _synthetic()
    if fault == "extra_program":       # a rung program no span launched
        st.spans = [s for s in st.spans if s.start != 900 * MS]
        st.base.modules.append((800 * MS, 850 * MS, "jit__lambda"))
    elif fault == "span_launched_nothing":
        st.base.host = [h for h in st.base.host if h[1] != 450 * MS]
    else:                               # 140 ms before the last launch
        st.base.modules.append((800 * MS, 850 * MS, "jit__lambda"))
    assert sp.join(st, "rung") is None
    red = sp.reduce(st)
    assert red.rungs is None and red.rungs_joined is None
    assert red.rung_queue_s is None
    got = sp.metrics({}, red)
    assert got["delta_lane_device_ms"] is None
    assert got["full_lane_device_ms"] is None
    assert got["rung_queue_ms"] is None
    assert red.commit_queue_s == pytest.approx(0.08)    # its own join


def test_launch_of_a_span_open_when_the_trace_stopped_is_joined():
    """A commit still running at the stop has no ``repro.apply`` event, but
    its launch and program are in the trace: joined, under kind ``?``."""
    st = _synthetic()
    st.base.host.append((UPDATER, 980 * MS, 985 * MS,
                         "PjitFunction(apply_batch)"))
    st.base.modules.append((990 * MS, 1100 * MS, "jit_apply_batch"))
    joined = sp.join(st, "apply")
    assert [(j.span is None, j.start) for j in joined] == [
        (False, 400 * MS), (True, 990 * MS)]
    assert sp.reduce(st).commit_queue_s == pytest.approx(0.045)
    # a launch outside the spans before the thread's last span is a fault
    st.base.host.append((UPDATER, 200 * MS, 210 * MS,
                         "PjitFunction(apply_batch)"))
    assert sp.join(st, "apply") is None


def test_queue_waits_of_rung_and_commit_programs():
    red = sp.reduce(_synthetic())
    assert red.rung_queue_s == pytest.approx(0.02)      # (20 + 20) / 2
    assert red.commit_queue_s == pytest.approx(0.08)    # 400 - 320
    # a program started after the window's close is joined, not averaged
    st = _synthetic()
    st.base.host[0] = (CLIENT, 0, 450 * MS, "bench.window")
    assert sp.reduce(st).rung_queue_s == pytest.approx(0.02)


def test_self_time_and_counts():
    red = sp.reduce(_synthetic())
    assert red.count == {"dispatch": 2, "classify": 2, "rung": 3,
                         "finish": 2, "commit": 1, "apply": 1,
                         "ring_commit": 1}
    assert red.self_s["dispatch"] == pytest.approx(0.08 + 0.14)
    assert red.self_s["classify"] == pytest.approx(0.05)
    assert red.self_s["commit"] == pytest.approx(0.34)
    assert red.lanes["classify"] == 7
    got = sp.metrics({}, red)
    assert got["classify_ms"] == pytest.approx(50 / 7)
    assert got["delta_lane_device_ms"] == pytest.approx(200 / 3)
    assert got["full_lane_device_ms"] == pytest.approx(300 / 4)
    assert got["rung_queue_ms"] == pytest.approx(20)
    assert got["commit_queue_ms"] == pytest.approx(80)


def test_idle_gaps_named_by_the_innermost_span():
    labels = dict(sp.reduce(_synthetic()).idle_by_label)
    assert labels == pytest.approx({
        "client.wait | repro.dispatch | " + LAUNCH: 0.2,
        "client.wait | repro.rung": 0.01,            # commit, dispatch too
        "client.wait | " + LAUNCH: 0.23})            # no span covers half


def test_counter_readings():
    c = {"picked": 8, "queue_wait_us": 4000, "lanes_run": 6, "pad_lanes": 2}
    got = sp.metrics(c, None)
    assert got["queue_wait_ms"] == pytest.approx(0.5)
    assert got["pad_share"] == pytest.approx(25.0)


@pytest.mark.parametrize("counters", [
    {},                                              # the parent's program
    {"picked": 0, "queue_wait_us": 0, "lanes_run": 0, "pad_lanes": 0}])
def test_readings_are_none_untraced_or_with_zero_counts(counters):
    assert set(sp.metrics(counters, None).values()) == {None}
    empty = sp.reduce(sp.Spans(
        tr.Trace(host=[(0, 0, 100, "bench.window")]), []))
    assert empty.rungs == {} and empty.rungs_joined == 0
    assert set(sp.metrics(counters, empty).values()) == {None}


def test_serve_counters_reads_what_the_front_end_has():
    assert sp.serve_counters(NS(stats=NS(picked=3, queue_wait_us=7,
                                         lanes_run=2, pad_lanes=0,
                                         dispatches=1))) == {
        "picked": 3, "queue_wait_us": 7, "lanes_run": 2, "pad_lanes": 0}
    assert sp.serve_counters(NS(stats=NS(dispatches=1))) == {}


def _profile(with_spans: bool):
    """A recorded-trace shape: two host threads, one device."""
    def ev(name, s, d, **stats):
        return NS(name=name, start_ns=s, duration_ns=d, stats=stats)

    dispatcher = [ev("PjitFunction(<lambda>)", 30, 5)]
    if with_spans:
        dispatcher += [ev("repro.dispatch", 10, 60, kind="bfs"),
                       ev("repro.rung", 20, 20, kind="bfs", rung="full",
                          lanes=1, pad=0)]
    return NS(planes=[
        NS(name="/host:CPU", lines=[
            NS(name="main", events=[ev("bench.window", 0, 100)]),
            NS(name="dispatcher", events=dispatcher)]),
        NS(name="/device:TPU:0", lines=[
            NS(name=tr.OPS_LINE, events=[ev("%fusion", 40, 30)]),
            NS(name=tr.MODULES_LINE, events=[ev("jit__lambda(7)", 40, 30)])]),
    ])


def test_from_profile_keeps_spans_and_stats_on_the_base_threads():
    st = sp.from_profile(_profile(True))
    assert [(s.thread, s.name, s.stats.get("kind")) for s in st.spans] == \
        [(1, "dispatch", "bfs"), (1, "rung", "bfs")]
    assert [h[0] for h in st.base.host if h[3].startswith("Pjit")] == [1]
    red = sp.reduce(st)
    assert red.rungs.keys() == {"bfs/full"}
    assert red.rungs["bfs/full"] == pytest.approx(
        {"device_s": 30e-9, "dispatches": 1, "lanes": 1, "pads": 0})
    assert red.rung_queue_s == pytest.approx(10e-9)


def test_spans_leave_the_existing_reduction_as_it_was():
    """``bench.trace`` reads the same busy time, programs, window and idle
    gaps from a trace that also holds the program's spans."""
    with_spans = tr.reduce(tr.from_profile(_profile(True)))
    without = tr.reduce(tr.from_profile(_profile(False)))
    assert with_spans == without
    assert with_spans.busy_s == pytest.approx(30e-9)
    assert with_spans.programs == pytest.approx({"jit__lambda": 30e-9})
