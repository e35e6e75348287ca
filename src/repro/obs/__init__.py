"""Unified telemetry: metrics, tracing, cost accounting, and control.

One instrument surface for the whole serving ladder (ROADMAP: the
measurement substrate the serving/ingest work is judged against):

  * :mod:`repro.obs.metrics` — counters / gauges / p50-p95-p99 histograms
    in a :class:`MetricsRegistry`; the engine's ad-hoc tally objects
    (``ServiceStats``, ``bc_scores_stats``, ``refresh_stats``,
    ``SchedulerStats``) are now attribute shims over it;
  * :mod:`repro.obs.trace` — span-based tracing with contextvar nesting
    and size-rotated JSONL export; every ``query()`` through either
    service emits a record carrying kind / ring version / ladder mode /
    wall time / collective bytes, with child spans for scheduler
    commits, tile refresh, and each collect of the PG-Cn loop; every
    span is also a ``jax.profiler.TraceAnnotation`` (``repro.<name>``),
    traced or not, so a profiler session sees the served path's phases
    on the device trace's clock, where device time is read;
  * :mod:`repro.obs.hlo` — compiled-program cost accounting
    (``cost_analysis`` / ``memory_analysis`` / HLO collective-byte
    parsing) cached per program signature and attributed to every
    query — sharded *and* local since PR 8;
  * :mod:`repro.obs.expo` — OpenMetrics exposition of the registry,
    served live (:meth:`Telemetry.serve`) or one-shot
    (``python -m repro.obs.expo``), so scrapes and ``BENCH_*.json``
    read the same surface;
  * :mod:`repro.obs.adaptive` — the :class:`AdaptiveThresholds`
    controller that closes the loop: it fits the delta-vs-full crossover
    from the service's own latency/dirty-fraction observations and tunes
    the ladder's ``dirty_threshold`` per kind within clamps;
  * :mod:`repro.obs.report` — ``python -m repro.obs.report TRACE.jsonl``
    renders the per-kind/per-mode summary table (and is the CI gate over
    traced streams).

:class:`Telemetry` bundles the runtime pieces; pass one to a service
(``GraphService(..., telemetry=Telemetry.make())``) to turn the
instruments on.  Without one, services still tally their shim counters
(each shim owns a private registry) but trace nothing and never compile
for accounting — the off path stays a single ``None`` check per query.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from .adaptive import AdaptiveThresholds  # noqa: F401
from .hlo import (  # noqa: F401
    HLOCostAccountant,
    account_jit,
    analyze_compiled,
    parse_collective_bytes,
)
from .metrics import (  # noqa: F401
    LADDER_MODES,
    Counter,
    CounterStruct,
    Gauge,
    Histogram,
    MetricsRegistry,
    ModeCounters,
    quantile,
)
from .trace import TRACE_SCHEMA, Span, Tracer, annotate, current_span, maybe_span  # noqa: F401


@dataclass
class Telemetry:
    """The bundle a service consumes: registry + tracer + accountant.

    ``block``: when True (default) a traced query blocks its result before
    the span closes, so the histogram / trace wall times are end-to-end
    device latencies (what a serving benchmark quotes as p50/p99), not
    dispatch times.  Callers that pipeline async dispatches can turn it
    off and keep tracing.
    """

    registry: MetricsRegistry = field(default_factory=MetricsRegistry)
    tracer: Tracer = field(default_factory=Tracer)
    accountant: Optional[HLOCostAccountant] = field(
        default_factory=HLOCostAccountant)
    block: bool = True

    @classmethod
    def make(cls, trace_path: Optional[str] = None, *, block: bool = True,
             hlo: bool = True,
             trace_max_bytes: Optional[int] = None,
             trace_keep: int = 3) -> "Telemetry":
        """One-call construction: in-memory by default, JSONL-sinking when
        ``trace_path`` is given (size-rotated at ``trace_max_bytes``,
        keeping ``trace_keep`` rotated files); ``hlo=False`` skips cost
        accounting (no extra compiles — e.g. compile-latency-sensitive
        tests)."""
        return cls(registry=MetricsRegistry(),
                   tracer=Tracer(path=trace_path, max_bytes=trace_max_bytes,
                                 keep=trace_keep),
                   accountant=HLOCostAccountant() if hlo else None,
                   block=block)

    def serve(self, port: int = 0, *, host: str = "127.0.0.1",
              journal=None):
        """Start the OpenMetrics scrape endpoint (``GET /metrics``) on a
        daemon thread; returns the :class:`repro.obs.expo.ExpoServer`
        (``.url``, ``.port``, ``.close()``).  ``journal`` additionally
        exposes the WAL depth gauge."""
        from .expo import ExpoServer
        return ExpoServer(self, port=port, host=host, journal=journal)

    def exposition(self, journal=None) -> str:
        """The current OpenMetrics exposition text (what a scrape of
        :meth:`serve` returns right now)."""
        from .expo import telemetry_exposition
        return telemetry_exposition(self, journal=journal)

    def close(self) -> None:
        self.tracer.close()
