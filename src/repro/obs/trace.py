"""Span-based tracing with JSONL export.

A :class:`Tracer` turns instrumented regions into flat trace records: each
``with tracer.span("query", kind="bfs") as sp`` emits one dict carrying the
span name, its wall time, an ``id``/``parent`` pair (nesting is tracked
through a :mod:`contextvars` variable, so spans opened anywhere down the
call stack — scheduler commits, tile refreshes, collect loops — attach to
the enclosing query span without threading a handle through every layer),
and whatever attributes the region set.  Records are kept in memory
(``tracer.records``, bounded) and, when a path is given, appended to a
JSONL file that ``python -m repro.obs.report`` renders into the
per-kind/per-mode summary table.

:func:`annotate` is the deliberately tiny hook the engine internals use:
it sets attributes on the *current* span if one is active and costs one
contextvar read otherwise — so ``engine.incremental`` can report dirty
counts without knowing whether anyone is tracing.

Telemetry is best-effort by design: a failing JSONL sink (disk full,
rotated-away file, or the injected ``obs.sink`` fault) must never fail
the query it was observing.  ``_emit`` swallows sink ``OSError``s and
injected faults, keeps the in-memory record, and counts the loss in
``tracer.sink_errors``.

The sink itself is bounded (the WAL's bug class: an append-only file on
a long stream grows without limit): with ``max_bytes`` set, a write that
would cross the limit first rotates ``trace.jsonl`` → ``trace.jsonl.1``
(shifting older rotations up to ``keep``, dropping the oldest) and
reopens fresh — counted in ``tracer.rotations``.

Every span is also a ``jax.profiler.TraceAnnotation`` named
``repro.<name>`` (:data:`PROFILER_PREFIX`), whether or not a tracer is
attached, whenever a profiler session is collecting: it lands on the
device trace's clock and its scalar attributes travel as the event's
stats.  With no session an annotation would be inert, so none is built:
untraced code pays one session check and a ``None`` check per span.

Thread-safety: span *nesting* is already per-thread for free
(:mod:`contextvars` — each serving thread sees its own current-span
stack), but id assignment and record emission mutate shared tracer
state, so both run under a tracer lock; interleaved spans from the
dispatcher and the committer each come out as complete, well-parented
records.
"""
from __future__ import annotations

import contextvars
import json
import os
import threading
import time
from contextlib import contextmanager, nullcontext
from typing import IO, Optional

from jax.profiler import TraceAnnotation

from repro.resil.faults import P_OBS_SINK, InjectedFault, inject

__all__ = ["PROFILER_PREFIX", "TRACE_SCHEMA", "Span", "Tracer", "annotate",
           "current_span", "maybe_span"]

#: bump when the record layout changes; readers reject unknown majors.
#: 2: query spans additionally carry device_us + flops.
#: 3: device_us is gone (device time comes from the profiler's trace).
TRACE_SCHEMA = 3

#: prefix of every span's profiler annotation (``repro.dispatch``, ...).
PROFILER_PREFIX = "repro."

_CURRENT: contextvars.ContextVar = contextvars.ContextVar(
    "repro_obs_span", default=None)


def current_span() -> Optional["Span"]:
    return _CURRENT.get()


def _scalars(attrs: dict) -> dict:
    """The attributes a profiler event can carry as stats."""
    return {k: v for k, v in attrs.items()
            if isinstance(v, (bool, int, float, str))}


_NO_ANNOTATION = nullcontext()


def _annotation(name: str, attrs: dict):
    """The profiler event of span ``name`` (the only place one is built);
    with no profiler session collecting, an event would be inert, so none
    is built."""
    if not TraceAnnotation.is_enabled():
        return _NO_ANNOTATION
    return TraceAnnotation(PROFILER_PREFIX + name, **_scalars(attrs))


def _forward(ann: Optional[TraceAnnotation], attrs: dict) -> None:
    """Late attributes of a span onto its profiler event, if it has one."""
    if ann is not None:
        ann.set_metadata(**_scalars(attrs))


def annotate(**attrs) -> None:
    """Attach attributes to the innermost active span (no-op untraced)."""
    sp = _CURRENT.get()
    if sp is not None:
        sp.set(**attrs)


class Span:
    """One open region; becomes a single trace record on exit."""

    __slots__ = ("name", "id", "parent", "attrs", "t0", "wall_us", "ann")

    def __init__(self, name: str, span_id: int, parent: Optional[int],
                 attrs: dict):
        self.name = name
        self.id = span_id
        self.parent = parent
        self.attrs = attrs
        self.t0 = time.perf_counter()
        self.wall_us = 0.0
        self.ann: Optional[TraceAnnotation] = None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        _forward(self.ann, attrs)

    def setdefault(self, **attrs) -> None:
        new = {k: v for k, v in attrs.items() if k not in self.attrs}
        self.attrs.update(new)
        _forward(self.ann, new)


class Tracer:
    """Collects span records; optionally streams them to a JSONL file.

    ``max_records`` bounds the in-memory list (oldest dropped) so an
    always-on tracer cannot grow a long-lived service without bound; the
    JSONL sink, when given, sees every record regardless.
    """

    def __init__(self, path: Optional[str] = None, max_records: int = 100000,
                 max_bytes: Optional[int] = None, keep: int = 3):
        self.path = path
        self.max_records = max_records
        self.max_bytes = max_bytes
        self.keep = max(1, keep)
        self.records: list = []
        self.dropped = 0
        self.sink_errors = 0
        self.rotations = 0
        self._next_id = 0
        self._t0 = time.perf_counter()
        self._lock = threading.Lock()
        self._sink: Optional[IO] = open(path, "a") if path else None
        self._sink_bytes = (os.path.getsize(path)
                            if path and os.path.exists(path) else 0)

    @contextmanager
    def span(self, name: str, **attrs):
        with self._lock:
            span_id = self._next_id
            self._next_id += 1
        sp = Span(name, span_id, getattr(_CURRENT.get(), "id", None), attrs)
        token = _CURRENT.set(sp)
        try:
            with _annotation(name, attrs) as sp.ann:
                yield sp
        finally:
            _CURRENT.reset(token)
            sp.wall_us = (time.perf_counter() - sp.t0) * 1e6
            self._emit(sp)

    def _emit(self, sp: Span) -> None:
        rec = {"schema": TRACE_SCHEMA, "span": sp.name, "id": sp.id,
               "parent": sp.parent,
               "t_s": round(sp.t0 - self._t0, 6),
               "wall_us": round(sp.wall_us, 1)}
        rec.update(sp.attrs)
        with self._lock:
            if len(self.records) >= self.max_records:
                self.records.pop(0)
                self.dropped += 1
            self.records.append(rec)
            if self._sink is None:
                return
            try:
                inject(P_OBS_SINK)
                line = json.dumps(rec) + "\n"
                if (self.max_bytes is not None and self._sink_bytes > 0
                        and self._sink_bytes + len(line) > self.max_bytes):
                    self._rotate()
                self._sink.write(line)
                self._sink.flush()
                self._sink_bytes += len(line)
            except (OSError, ValueError, InjectedFault):
                # Best-effort sink: losing a trace line must never fail
                # the observed operation.  The in-memory record survives.
                self.sink_errors += 1

    def _rotate(self) -> None:
        """Shift ``path`` → ``path.1`` → ... → ``path.keep`` (oldest
        dropped) and reopen fresh.  A failing rename is swallowed — the
        sink reopens on whatever file is there (possibly still the
        oversized one) and the caller's record is appended regardless, so
        a stuck filesystem degrades to an unrotated file, never to a
        dead or lossy trace stream."""
        self._sink.close()
        try:
            oldest = f"{self.path}.{self.keep}"
            if os.path.exists(oldest):
                os.remove(oldest)
            for i in range(self.keep - 1, 0, -1):
                src = f"{self.path}.{i}"
                if os.path.exists(src):
                    os.replace(src, f"{self.path}.{i + 1}")
            os.replace(self.path, f"{self.path}.1")
            self.rotations += 1
        except OSError:
            pass
        finally:
            self._sink = open(self.path, "a")
            self._sink_bytes = os.path.getsize(self.path)

    def close(self) -> None:
        if self._sink is not None:
            self._sink.close()
            self._sink = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


@contextmanager
def maybe_span(tracer: Optional[Tracer], name: str, **attrs):
    """``tracer.span`` when tracing, else a span that records nothing and
    only opens the profiler annotation — so instrumented code writes one
    code path and, with telemetry off, still shows on a profiler trace."""
    if tracer is None:
        with _annotation(name, attrs) as ann:
            yield _NULL_SPAN if ann is None else _ProfilerSpan(ann)
    else:
        with tracer.span(name, **attrs) as sp:
            yield sp


class _ProfilerSpan:
    """The span of an untraced region: attributes go to the profiler
    event alone."""

    __slots__ = ("ann",)
    id = None
    wall_us = 0.0

    def __init__(self, ann: Optional[TraceAnnotation]):
        self.ann = ann

    def set(self, **attrs) -> None:
        _forward(self.ann, attrs)

    def setdefault(self, **attrs) -> None:
        _forward(self.ann, attrs)


_NULL_SPAN = _ProfilerSpan(None)
