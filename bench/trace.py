"""Reduce a profiler trace to device busy time, per-program device time and
idle gaps attributed to what the benchmark's threads were doing.

Device work is read from the device planes (``/device:TPU:<i>``): the
``XLA Ops`` line gives the intervals in which an operation ran (their
union is busy time) and the operation's name, the ``XLA Modules`` line one
event per program execution (its name, less the ``(<id>)`` suffix, is the
program).  Async copies sit on a line of their own inside a program's span
and are not counted as busy; on the chip the ops line covers the programs'
spans.  Host spans are the benchmark's own ``TraceAnnotation``s (names
starting with ``bench.``) and JAX's ``PjitFunction(<name>)`` dispatch
events, from the host plane's threads.

Every reading over devices is the mean over the devices traced: busy time,
seconds per program, and the time a device spent in collectives (the
``all-reduce``, ``all-gather``, ``collective-permute``, ``reduce-scatter``
and ``all-to-all`` operations, with their ``-start`` / ``-done`` halves),
in all and where no other operation ran on that device (exposed).
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
_SUFFIX = re.compile(r"\(\d+\)$")
#: an HLO collective, as named without its ``.<id>``
_COLLECTIVE = re.compile(r"(all-reduce|all-gather|collective-permute|"
                         r"reduce-scatter|all-to-all)(-start|-done)?$")
#: control flow: an event that spans the ops it runs, so it covers nothing
_CONTAINER = re.compile(r"(while|conditional|call)$")
_OP_ID = re.compile(r"\.\d+$")


@dataclass
class Trace:
    """A trace as plain intervals (nanoseconds on the profiler's clock)."""

    #: per device: intervals in which an operation ran
    ops: List[List[Tuple[float, float]]] = field(default_factory=list)
    #: per device: the name of each of those operations, where read
    op_names: List[List[str]] = field(default_factory=list)
    modules: List[Tuple[float, float, str]] = field(default_factory=list)
    #: host spans: (thread index, start, end, name)
    host: List[Tuple[int, float, float, str]] = field(default_factory=list)


def program_name(name: str) -> str:
    return _SUFFIX.sub("", name)


def op_kind(name: str) -> str:
    """An op event's HLO opcode-like name: ``collective-permute-done`` for
    ``%collective-permute-done.7`` or for the chip's
    ``collective-permute-done.3 = s32[128,512]{...} collective-permute-done(...)``."""
    return _OP_ID.sub("", name.lstrip("%").split(" = ", 1)[0])


def collective_name(name: str) -> str:
    """The collective an op event runs, or ``""`` for one that is none."""
    kind = op_kind(name)
    return kind if _COLLECTIVE.match(kind) else ""


def read_profile(logdir: str):
    """The newest ``.xplane.pb`` under ``logdir``, as ``ProfileData``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no profiler trace under {logdir}")
    return ProfileData.from_file(paths[-1])


def load(logdir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``logdir``."""
    return from_profile(read_profile(logdir))


def from_profile(pd) -> Trace:
    """Device planes are the ``/device:`` planes with an ``XLA Ops`` line:
    a TPU host also writes planes such as ``/device:CUSTOM:Megascale
    Trace`` that hold no device work, and counting them as devices would
    halve the busy time averaged over devices."""
    tr = Trace()
    thread = 0
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:") and OPS_LINE in lines:
            events = list(lines[OPS_LINE].events)
            tr.ops.append([(e.start_ns, e.start_ns + e.duration_ns)
                           for e in events])
            tr.op_names.append([e.name for e in events])
            if MODULES_LINE in lines:
                tr.modules += [(e.start_ns, e.start_ns + e.duration_ns,
                                program_name(e.name))
                               for e in lines[MODULES_LINE].events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if (e.name.startswith(BENCH_PREFIX)
                            or e.name.startswith("PjitFunction(")):
                        tr.host.append((thread, e.start_ns,
                                        e.start_ns + e.duration_ns, e.name))
                thread += 1
    return tr


def window(tr: Trace) -> Tuple[float, float]:
    """The measured window: the benchmark's ``bench.window`` span."""
    spans = [(s, e) for _, s, e, n in tr.host if n == WINDOW_SPAN]
    if not spans:
        raise ValueError("trace has no bench.window span")
    return spans[0]


def union(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Merged intervals clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: float, hi: float) -> List[Tuple[float, float]]:
    """Idle intervals of ``[lo, hi]`` between the merged ``busy`` ones."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def covered(a, b) -> float:
    """Length of the merged intervals ``a`` that the merged ``b`` cover."""
    out, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return out


def program_seconds(tr: Trace, lo: float, hi: float) -> Dict[str, float]:
    """Device seconds per program inside ``[lo, hi]``, averaged over the
    devices traced (an SPMD program on four devices counts its time on
    one)."""
    acc: Dict[str, float] = defaultdict(float)
    n = max(1, len(tr.ops))
    for s, e, name in tr.modules:
        d = min(e, hi) - max(s, lo)
        if d > 0:
            acc[name] += d / 1e9 / n
    return dict(acc)


@dataclass
class Collectives:
    """Collective time of a window, averaged over the devices traced."""

    seconds: float                # union of the collectives' intervals
    exposed_s: float              # ...less where another operation ran
    ops: Dict[str, float]         # seconds per collective (no ``.<id>``)


def collectives(tr: Trace, lo: float, hi: float) -> Collectives:
    """Per device, the union of its collective operations' intervals and
    the part of it that the union of its other operations leaves
    uncovered, then the mean of each over the devices.  A control-flow op
    (``while``, ``conditional``, ``call``) spans the ops it runs, the
    collectives among them, so it is counted as neither."""
    total = exposed = 0.0
    ops: Dict[str, float] = defaultdict(float)
    n = max(1, len(tr.ops))
    for dev, names in zip(tr.ops, tr.op_names):
        coll, other = [], []
        for (s, e), name in zip(dev, names):
            kind = collective_name(name)
            if kind:
                coll.append((s, e))
                d = min(e, hi) - max(s, lo)
                if d > 0:
                    ops[kind] += d / 1e9 / n
            elif not _CONTAINER.match(op_kind(name)):
                other.append((s, e))
        c = union(coll, lo, hi)
        held = sum(e - s for s, e in c)
        total += held / 1e9 / n
        exposed += (held - covered(c, union(other, lo, hi))) / 1e9 / n
    return Collectives(total, exposed, dict(ops))


def _activity(name: str) -> str:
    """``bench.client.wait`` -> ``client.wait``."""
    return name[len(BENCH_PREFIX):]


class _HostIndex:
    """The host spans as arrays, for labelling many gaps at once."""

    def __init__(self, tr: Trace):
        spans = [h for h in tr.host if h[3] != WINDOW_SPAN]
        self.names = [h[3] for h in spans]
        self.start = np.array([h[1] for h in spans], np.float64)
        self.end = np.array([h[2] for h in spans], np.float64)
        self.bench = np.array([n.startswith(BENCH_PREFIX)
                               for n in self.names], bool)

    def label(self, s: float, e: float) -> str:
        """What the host was doing in the idle gap ``[s, e]``: every
        benchmark activity that covers at least half of it, and the JAX
        dispatch that overlaps it most (the program's own threads carry
        only those)."""
        if not self.names:
            return "none"
        ov = np.minimum(self.end, e) - np.maximum(self.start, s)
        acts = sorted({_activity(self.names[i]) for i in
                       np.flatnonzero(self.bench & (ov >= (e - s) / 2))})
        label = "+".join(acts) or "none"
        other = np.where(self.bench, 0.0, ov)
        i = int(np.argmax(other))
        return f"{label} | {self.names[i]}" if other[i] > 0 else label


@dataclass
class Reduced:
    window_s: float
    busy_s: float
    programs: Dict[str, float]
    idle_by_label: List[Tuple[str, float]]        # the first device's
    #: every device's labelled idle gaps, the first device's first
    idle_by_device: List[List[Tuple[str, float]]]
    collectives: Collectives


def _labelled_idle(busy, lo: float, hi: float, index: _HostIndex, top: int,
                   labelled: int) -> List[Tuple[str, float]]:
    """Idle gaps of one device, the ``labelled`` longest attributed to the
    host, summed per label, the ``top`` largest sums."""
    idle = gaps(busy, lo, hi)
    by_label: Dict[str, float] = defaultdict(float)
    lengths = np.array([e - s for s, e in idle])
    order = np.argsort(-lengths, kind="stable")
    for i in order[:labelled]:
        s, e = idle[i]
        by_label[index.label(s, e)] += (e - s) / 1e9
    if len(order) > labelled:
        by_label["shorter gaps"] = float(lengths[order[labelled:]].sum()) / 1e9
    return sorted(by_label.items(), key=lambda kv: -kv[1])[:top]


def reduce(tr: Trace, top: int = 10, labelled: int = 1000) -> Reduced:
    """Busy time of the window averaged over the devices traced; idle gaps
    of each device, the ``labelled`` longest attributed to the host."""
    lo, hi = window(tr)
    per_dev = [union(ops, lo, hi) for ops in tr.ops] or [[]]
    busy_s = float(np.mean([sum(e - s for s, e in b) for b in per_dev])) / 1e9
    index = _HostIndex(tr)
    idle = [_labelled_idle(b, lo, hi, index, top, labelled) for b in per_dev]
    return Reduced((hi - lo) / 1e9, busy_s, program_seconds(tr, lo, hi),
                   idle[0], idle, collectives(tr, lo, hi))
