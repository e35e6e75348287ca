"""The program's own spans in a profiler trace, and the device programs
they launched.

Every span of the program (``repro.obs.trace``) is a profiler annotation
named ``repro.<name>`` whose attributes are the event's stats, traced or
not.  Next to ``bench.trace``'s reduction this module reads:

  * the self time (less the ``repro.`` spans nested in it on its thread)
    and the count of each span started in the window, and the sum of its
    ``lanes`` stat;
  * the join: device programs matched to the spans that launched them, in
    launch order from the trace's start.  ``jit__lambda`` (every rung
    program of ``serve/batch.py``) joins to the ``PjitFunction(<lambda>)``
    launches inside ``repro.rung`` spans, ``jit_apply_batch`` to the
    ``PjitFunction(apply_batch)`` launches inside ``repro.apply``.  One chip
    runs its programs in launch order and the profiler starts after set-up
    has drained, so the k-th launch ran as the k-th program.  Launches at
    the trace's end whose program never started are dropped; a launch at
    the end of a thread whose span was still open when the trace stopped
    (and so is not in it) is joined under kind and rung ``?``.  More
    programs than launches, a span that launched nothing, a launch outside
    the spans elsewhere, or a program that starts before its launch (by
    more than the clocks' alignment) leaves the join unmade (``None``),
    never estimated;
  * per (kind, rung): device seconds in the window, dispatches, lanes and
    padding lanes; a program straddling an edge of the window counts by
    the share of its device time inside;
  * device-queue wait: a joined program's start minus the start of its
    launch event, averaged over the programs started in the window.  The
    launch is counted in: on one v5e a launch blocks while the device
    runs another program (cold's ``apply_batch`` launches took 0.86 s on
    average), so the wait sits inside the launch as much as after it.  A
    program launched onto an idle device reads the launch's own length
    (about a millisecond), within the clocks' alignment;
  * idle gaps named after the innermost ``repro.`` span that covers most
    of the gap, between the benchmark's activities and the JAX dispatch
    that ``bench.trace`` names.

``serve_counters`` reads the front end's counters of the same path
(``ServeStats``); ``metrics`` turns both into per-layer readings.  The
harness does not call this module yet: ``bench/harness.py`` and
``bench/trace.py`` keep neither the counters nor the ``repro.`` events.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from . import trace

PREFIX = "repro."
#: span -> (device program it launches, host event of the launch)
JOINS = {"rung": ("jit__lambda", "PjitFunction(<lambda>)"),
         "apply": ("jit_apply_batch", "PjitFunction(apply_batch)")}
#: how far a device program may read as starting before its launch: the
#: profiler aligns the device's clock to the host's within about a
#: millisecond (up to 0.8 ms early on one v5e), while launches that a
#: misaligned join would pair lie tens of milliseconds or more apart
CLOCK_SLACK_NS = 5e6
#: ``ServeStats`` counters of the served path
COUNTERS = ("picked", "queue_wait_us", "lanes_run", "pad_lanes")


@dataclass
class Span:
    thread: int
    start: float
    end: float
    name: str                     # without the ``repro.`` prefix
    stats: dict


@dataclass
class Spans:
    base: trace.Trace             # what ``bench.trace`` reads
    spans: List[Span]


def from_profile(pd) -> Spans:
    """``bench.trace.from_profile`` and the ``repro.`` host events, with
    threads numbered as it numbers them."""
    spans, thread = [], 0
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [Span(thread, e.start_ns, e.start_ns + e.duration_ns,
                               e.name[len(PREFIX):], dict(e.stats))
                          for e in ln.events if e.name.startswith(PREFIX)]
                thread += 1
    return Spans(trace.from_profile(pd), spans)


@dataclass
class Joined:
    span: Optional[Span]          # None: open when the trace stopped
    launch: float                 # start of the launch event
    start: float                  # the program on the device
    end: float


def _launches(st: Spans, name: str,
              launcher: str) -> Optional[List[tuple]]:
    """``(start, end, span)`` of every outermost ``launcher`` event (the
    host trace may nest one dispatch event in another of the same name),
    in launch order.  ``span`` is the ``name`` span around it on its
    thread, or ``None`` for a launch after that thread's last such span:
    one still open when the trace stopped, which the trace does not hold.
    ``None`` when a span holds no launch or any other launch has none."""
    spans: Dict[int, List[Span]] = defaultdict(list)
    for sp in st.spans:
        if sp.name == name:
            spans[sp.thread].append(sp)
    evs: Dict[int, List[tuple]] = defaultdict(list)
    for th, s, e, n in st.base.host:
        if n == launcher:
            evs[th].append((s, e))
    out, used = [], set()
    for th, mine in evs.items():
        around = sorted(spans.get(th, []), key=lambda sp: sp.start)
        starts = [sp.start for sp in around]
        last = max((sp.end for sp in around), default=-np.inf)
        outer = -np.inf
        for s, e in sorted(mine):
            if e <= outer:
                continue
            outer = e
            k = bisect.bisect_right(starts, s) - 1
            sp = around[k] if k >= 0 and e <= around[k].end else None
            if sp is None and s < last:
                return None
            used.add(id(sp))
            out.append((s, e, sp))
    if any(id(sp) not in used for th in spans for sp in spans[th]):
        return None
    return sorted(out, key=lambda x: x[0])


def join(st: Spans, name: str) -> Optional[List[Joined]]:
    """The programs launched inside the ``name`` spans, in launch order,
    each with its span and device interval; ``None`` where the counts or
    the order cannot be reconciled."""
    program, launcher = JOINS[name]
    launches = _launches(st, name, launcher)
    mods = sorted((s, e) for s, e, n in st.base.modules if n == program)
    if launches is None or len(mods) > len(launches):
        return None
    out = []
    for (ls, _, sp), (ms, me) in zip(launches, mods):
        if ms < ls - CLOCK_SLACK_NS:
            return None
        out.append(Joined(sp, ls, ms, me))
    return out


def _inside(s: float, e: float, lo: float, hi: float) -> float:
    """Share of ``[s, e]`` inside ``[lo, hi]``."""
    return max(0.0, min(e, hi) - max(s, lo)) / (e - s) if e > s else 0.0


def _rungs(joined: List[Joined], lo: float, hi: float) -> Dict[str, dict]:
    acc: Dict[str, dict] = {}
    for j in joined:
        share = _inside(j.start, j.end, lo, hi)
        if share <= 0:
            continue
        stats = j.span.stats if j.span is not None else {}
        key = f"{stats.get('kind', '?')}/{stats.get('rung', '?')}"
        r = acc.setdefault(key, {"device_s": 0.0, "dispatches": 0.0,
                                 "lanes": 0.0, "pads": 0.0})
        r["device_s"] += share * (j.end - j.start) / 1e9
        r["dispatches"] += share
        r["lanes"] += share * stats.get("lanes", 0)
        r["pads"] += share * stats.get("pad", 0)
    return acc


def _queue_s(joined: Optional[List[Joined]], lo: float,
             hi: float) -> Optional[float]:
    """Mean launch-to-start wait of the joined programs started in the
    window."""
    if joined is None:
        return None
    waits = [j.start - j.launch for j in joined if lo <= j.start < hi]
    return float(np.mean(waits)) / 1e9 if waits else None


def _self_times(spans: List[Span]) -> List[float]:
    """Each span's duration less those of the spans directly nested in it
    on its thread (spans nest on a thread; they never cross)."""
    out = [sp.end - sp.start for sp in spans]
    by_thread: Dict[int, List[int]] = defaultdict(list)
    for i, sp in enumerate(spans):
        by_thread[sp.thread].append(i)
    for idx in by_thread.values():
        idx.sort(key=lambda i: (spans[i].start, -spans[i].end))
        stack: List[int] = []
        for i in idx:
            while stack and spans[i].end > spans[stack[-1]].end:
                stack.pop()
            if stack:
                out[stack[-1]] -= spans[i].end - spans[i].start
            stack.append(i)
    return out


class _SpanIndex:
    """The ``repro.`` spans as arrays, for naming many gaps at once."""

    def __init__(self, st: Spans):
        self.base = trace._HostIndex(st.base)
        self.names = [sp.name for sp in st.spans]
        self.start = np.array([sp.start for sp in st.spans], np.float64)
        self.end = np.array([sp.end for sp in st.spans], np.float64)

    def label(self, s: float, e: float) -> str:
        """``bench.trace``'s label with the innermost ``repro.`` span that
        covers at least half of ``[s, e]`` after the benchmark's
        activities."""
        base = self.base.label(s, e)
        if not self.names:
            return base
        ov = np.minimum(self.end, e) - np.maximum(self.start, s)
        cand = np.flatnonzero(ov >= (e - s) / 2)
        if not cand.size:
            return base
        # most of the gap first; among equals the shortest, the innermost
        i = min(cand, key=lambda k: (-ov[k], self.end[k] - self.start[k]))
        head, sep, tail = base.partition(" | ")
        return f"{head} | {PREFIX}{self.names[i]}{sep}{tail}"


@dataclass
class SpanReduced:
    self_s: Dict[str, float]      # span -> self seconds, started in window
    count: Dict[str, int]
    lanes: Dict[str, float]       # span -> sum of its ``lanes`` stat
    #: "<kind>/<rung>" -> device_s, dispatches, lanes, pads; None unjoined
    rungs: Optional[Dict[str, dict]]
    rungs_joined: Optional[int]   # joined rung programs in the window
    rung_queue_s: Optional[float]
    commit_queue_s: Optional[float]
    idle_by_label: List[Tuple[str, float]]


def reduce(st: Spans, top: int = 10, labelled: int = 1000) -> SpanReduced:
    lo, hi = trace.window(st.base)
    inside = [sp for sp in st.spans if lo <= sp.start < hi]
    self_s: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    lanes: Dict[str, float] = defaultdict(float)
    for sp, t in zip(inside, _self_times(inside)):
        self_s[sp.name] += t / 1e9
        count[sp.name] += 1
        lanes[sp.name] += sp.stats.get("lanes", 0)
    rungs = join(st, "rung")
    per_rung = None if rungs is None else _rungs(rungs, lo, hi)

    index = _SpanIndex(st)
    busy = trace.union(st.base.ops[0], lo, hi) if st.base.ops else []
    idle = trace.gaps(busy, lo, hi)
    lengths = np.array([e - s for s, e in idle])
    order = np.argsort(-lengths, kind="stable")
    by_label: Dict[str, float] = defaultdict(float)
    for i in order[:labelled]:
        s, e = idle[i]
        by_label[index.label(s, e)] += (e - s) / 1e9
    if len(order) > labelled:
        by_label["shorter gaps"] = float(lengths[order[labelled:]].sum()) / 1e9
    return SpanReduced(
        dict(self_s), dict(count), dict(lanes), per_rung,
        None if rungs is None else sum(
            1 for j in rungs if _inside(j.start, j.end, lo, hi) > 0),
        _queue_s(rungs, lo, hi), _queue_s(join(st, "apply"), lo, hi),
        sorted(by_label.items(), key=lambda kv: -kv[1])[:top])


def serve_counters(srv) -> Dict[str, int]:
    """The front end's counters of the served path, those it has."""
    return {k: int(getattr(srv.stats, k)) for k in COUNTERS
            if hasattr(srv.stats, k)}


def _lane_ms(red: Optional[SpanReduced], rung: str) -> Optional[float]:
    if red is None or red.rungs is None:
        return None
    picked = [r for k, r in red.rungs.items() if k.endswith("/" + rung)]
    lanes = sum(r["lanes"] for r in picked)
    return 1e3 * sum(r["device_s"] for r in picked) / lanes if lanes else None


def metrics(counters: Dict[str, int],
            red: Optional[SpanReduced]) -> Dict[str, Optional[float]]:
    """Per-layer readings of a window's counter deltas and its reduced
    trace (``None`` for an untraced run); ``None`` where a count is 0."""
    c = counters
    picked = c.get("picked", 0)
    run = c.get("lanes_run", 0) + c.get("pad_lanes", 0)
    cls_lanes = red.lanes.get("classify", 0) if red else 0
    return {
        "queue_wait_ms": c.get("queue_wait_us", 0) / picked / 1e3
        if picked else None,
        "pad_share": 100.0 * c.get("pad_lanes", 0) / run if run else None,
        "classify_ms": 1e3 * red.self_s["classify"] / cls_lanes
        if cls_lanes else None,
        "delta_lane_device_ms": _lane_ms(red, "delta"),
        "full_lane_device_ms": _lane_ms(red, "full"),
        "rung_queue_ms": None if red is None or red.rung_queue_s is None
        else 1e3 * red.rung_queue_s,
        "commit_queue_ms": None if red is None or red.commit_queue_s is None
        else 1e3 * red.commit_queue_s,
    }
