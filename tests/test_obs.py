"""Unit tests for the telemetry subsystem (``repro.obs``).

The integration path — a traced service stream asserting conservation and
oracle agreement — lives in ``test_stream_differential``; here the
instruments themselves are pinned: registry identity semantics, quantile
math, the attribute shims the legacy stats objects became, span nesting /
annotation, JSONL export through the ``repro.obs.report`` gate, and the
HLO cost accountant's compile-once cache.
"""
import json
import math

import jax
import jax.numpy as jnp
import pytest

from repro.obs import (
    CounterStruct,
    HLOCostAccountant,
    MetricsRegistry,
    ModeCounters,
    Telemetry,
    Tracer,
    report,
)
from repro.obs.metrics import quantile
from repro.obs.trace import TRACE_SCHEMA, annotate, maybe_span


# ------------------------------- metrics -----------------------------------

def test_registry_get_or_create_identity():
    reg = MetricsRegistry()
    a = reg.counter("hits", service="local")
    b = reg.counter("hits", service="local")
    c = reg.counter("hits", service="sharded")
    assert a is b and a is not c
    a.inc(3)
    assert b.value == 3 and c.value == 0
    # same name, different instrument kind -> distinct
    h = reg.histogram("hits")
    assert h is not a


def test_registry_find_and_merged_quantiles():
    reg = MetricsRegistry()
    for mode, vals in (("delta", [1, 2, 3]), ("full", [10, 20, 30])):
        h = reg.histogram("wall", service="local", mode=mode)
        for v in vals:
            h.observe(v)
    assert len(reg.find("wall", service="local")) == 2
    assert reg.find("wall", mode="delta")[0].count == 3
    pooled = reg.merged_quantiles("wall", (0.0, 0.5, 1.0), service="local")
    assert pooled[0.0] == 1 and pooled[1.0] == 30
    assert math.isnan(reg.merged_quantiles("absent", (0.5,))[0.5])


def test_quantile_nearest_rank():
    s = list(range(1, 101))
    assert quantile(s, 0.5) == 51  # nearest rank on 0..99 index space
    assert quantile(s, 0.0) == 1
    assert quantile(s, 1.0) == 100
    assert math.isnan(quantile([], 0.5))


def test_histogram_reservoir_bounded():
    reg = MetricsRegistry()
    h = reg.histogram("w")
    h._samples = type(h._samples)(maxlen=4)
    for v in range(10):
        h.observe(v)
    assert h.count == 10 and h.total == sum(range(10))
    assert h.samples == [6, 7, 8, 9]


def test_counter_struct_shim():
    class S(CounterStruct):
        _FIELDS = ("a", "b")
        _PREFIX = "test_"

    reg = MetricsRegistry()
    s = S(reg, service="x")
    s.a += 2
    s.a += 1
    s.b = 7
    assert (s.a, s.b) == (3, 7)
    assert s.as_dict() == {"a": 3, "b": 7}
    # the values ARE registry counters, shared by key
    assert reg.counter("test_a", service="x").value == 3
    # private registry when none is given
    s2 = S()
    s2.a += 1
    assert s2.a == 1 and reg.counter("test_a", service="x").value == 3


def test_mode_counters_mapping():
    reg = MetricsRegistry()
    d = ModeCounters(reg, "bcq", service="local")
    d["delta"] += 2
    d["full"] = 5
    assert dict(d) == {"unchanged": 0, "delta": 2, "full": 5}
    assert reg.counter("bcq", mode="delta", service="local").value == 2


# -------------------------------- tracing ----------------------------------

def test_tracer_nesting_and_annotate():
    tr = Tracer()
    with tr.span("query", kind="bfs") as q:
        with tr.span("collect") as c:
            annotate(dirty=4)  # lands on the innermost span
        q.set(mode="delta")
    annotate(ignored=1)  # no active span: silently dropped
    child, parent = tr.records  # children exit (emit) first
    assert parent["span"] == "query" and parent["parent"] is None
    assert child["span"] == "collect" and child["parent"] == parent["id"]
    assert child["dirty"] == 4 and "ignored" not in parent
    assert parent["mode"] == "delta" and parent["wall_us"] >= 0


def test_maybe_span_null_path():
    with maybe_span(None, "query", kind="bfs") as sp:
        sp.set(mode="full")  # must not raise
        annotate(dirty=1)    # no tracer: no-op
    assert sp.id is None


@pytest.mark.parametrize("traced", [False, True])
def test_span_is_a_profiler_annotation(tmp_path, traced):
    """Traced or not, a span is a ``repro.<name>`` profiler event whose
    stats carry its scalar attributes, those set late included."""
    import glob

    from jax.profiler import ProfileData

    tr = Tracer() if traced else None
    jax.profiler.start_trace(str(tmp_path))
    try:
        with maybe_span(tr, "rung", kind="bfs", lanes=3, skip=[1]) as sp:
            with maybe_span(tr, "inner"):
                pass
            sp.set(pad=1, ok=True)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                          "*.xplane.pb"))
    events = {e.name: dict(e.stats)
              for plane in ProfileData.from_file(path).planes
              for line in plane.lines for e in line.events
              if e.name.startswith("repro.")}
    assert set(events) == {"repro.rung", "repro.inner"}
    assert events["repro.rung"] == {"kind": "bfs", "lanes": 3, "pad": 1,
                                    "ok": True}
    assert (sp.id is not None) == traced
    if traced:
        assert [r["span"] for r in tr.records] == ["inner", "rung"]
        assert tr.records[1]["skip"] == [1]     # JSONL keeps every attr


def test_tracer_jsonl_and_report_gate(tmp_path):
    path = tmp_path / "t.jsonl"
    tr = Tracer(str(path))
    for mode in ("unchanged", "delta", "full"):
        with tr.span("query", service="local", kind="bfs", version=1,
                     mode=mode, coll_bytes=0, degraded=False,
                     flops=100.0):
            pass
    tr.close()
    records = report.load(str(path))
    assert [r["schema"] for r in records] == [TRACE_SCHEMA] * 3
    assert report.validate(
        records, require_modes=("unchanged", "delta", "full")) == []
    rows = report.summarize(records)
    assert {r["mode"] for r in rows} == {"unchanged", "delta", "full"}
    assert report.main([str(path), "--check",
                        "--require-modes", "unchanged,delta,full"]) == 0
    # missing mode and missing fields both trip the gate
    assert report.validate(records, require_modes=("nope",)) != []
    bad = [dict(r, **{"span": "query"}) for r in records]
    del bad[0]["version"]
    assert any("missing" in e for e in report.validate(bad))
    assert report.main([str(path), "--require-modes", "nope"]) == 1


# ---------------------------- HLO accounting --------------------------------

def test_hlo_accountant_caches_compiles():
    acct = HLOCostAccountant(shared=False)
    compiles = []

    def compile_fn():
        compiles.append(1)
        return jax.jit(lambda x: x * 2 + 1).lower(
            jnp.zeros((8,), jnp.float32)).compile()

    c1 = acct.account(("k", 1), compile_fn)
    c2 = acct.account(("k", 1), compile_fn)
    assert len(compiles) == 1 and c1 is c2 and acct.last is c2
    for f in ("collective_bytes", "temp_bytes", "flops"):
        assert f in c1
    assert acct.account(("k", 2), compile_fn) is not c1
    assert len(compiles) == 2
    assert len(acct.snapshot()) == 2


def test_hlo_accountant_shared_cache():
    a, b = HLOCostAccountant(), HLOCostAccountant()
    n0 = len(a.snapshot())
    a.account(("shared-probe", n0), lambda: jax.jit(lambda x: x + 1).lower(
        jnp.zeros((4,), jnp.float32)).compile())
    assert b.account(("shared-probe", n0), lambda: (_ for _ in ()).throw(
        AssertionError("cache miss"))) is a.last


# ----------------------------- service glue ---------------------------------

def test_local_service_trace_schema(tmp_path):
    from repro.core import PUTE, PUTV, make_graph
    from repro.engine import GraphService

    path = tmp_path / "svc.jsonl"
    tel = Telemetry.make(str(path), hlo=False)
    svc = GraphService(make_graph(16, 64), batch_size=4, telemetry=tel)
    for i in range(6):
        svc.submit((PUTV, i))
    for u, v in ((0, 1), (1, 2), (2, 3)):
        svc.submit((PUTE, u, v, 1.0))
    svc.flush()
    svc.query("bfs", 0)
    svc.query("bfs", 0)
    svc.submit((PUTE, 3, 4, 1.0))
    svc.flush()
    svc.query("bfs", 0)
    tel.close()

    records = [json.loads(line) for line in open(path)]
    qrecs = [r for r in records if r["span"] == "query"]
    assert len(qrecs) == svc.stats.queries == 3
    for r in qrecs:
        for f in report.QUERY_FIELDS:
            assert f in r, f
        assert r["service"] == "local"
    assert [r["mode"] for r in qrecs] == ["full", "unchanged", "delta"]
    # commits and collects traced too, collects nested under their query
    spans = {r["span"] for r in records}
    assert {"commit", "collect", "query"} <= spans
    collect = next(r for r in records if r["span"] == "collect")
    assert any(r["id"] == collect["parent"] for r in qrecs)
    # the latency histogram the benches read is fed once per query
    hist = tel.registry.find("query_wall_us", service="local")
    assert sum(h.count for h in hist) == 3


def test_local_service_device_and_flops_attribution(tmp_path):
    """With the accountant on, every local query span carries ``flops``
    from the compiled program that answered it (and zero collective
    bytes — the local engine has no collectives).  The unchanged shortcut
    runs no program, so its span legitimately reports zero flops.  Device
    time is the profiler trace's to give (schema 3): no record carries
    ``device_us`` and no ``query_device_us`` histogram is fed."""
    from repro.core import PUTE, PUTV, make_graph
    from repro.engine import GraphService

    path = tmp_path / "svc.jsonl"
    tel = Telemetry.make(str(path))
    svc = GraphService(make_graph(16, 64), batch_size=4, telemetry=tel)
    for i in range(6):
        svc.submit((PUTV, i))
    for u, v in ((0, 1), (1, 2), (2, 3)):
        svc.submit((PUTE, u, v, 1.0))
    svc.flush()
    svc.query("bfs", 0)   # full
    svc.query("bfs", 0)   # unchanged
    svc.submit((PUTE, 3, 4, 1.0))
    svc.flush()
    svc.query("bfs", 0)   # delta
    tel.close()

    qrecs = [json.loads(l) for l in open(path)]
    qrecs = [r for r in qrecs if r["span"] == "query"]
    assert [r["mode"] for r in qrecs] == ["full", "unchanged", "delta"]
    full, unchanged, delta = qrecs
    assert full["flops"] > 0 and delta["flops"] > 0
    assert unchanged["flops"] == 0        # no program dispatched
    for r in qrecs:
        assert r["coll_bytes"] == 0       # local engine: no collectives
        assert r["schema"] == TRACE_SCHEMA == 3
        assert "device_us" not in r
    assert full["wall_us"] > 0            # the full sweep really ran
    assert tel.registry.find("query_device_us", service="local") == []


# ------------------------- metrics edge cases (PR 8) ------------------------

def test_merged_quantiles_empty_reservoirs():
    """Histograms that exist but have no samples pool to NaN quantiles,
    and mixing an empty histogram into a populated pool is a no-op."""
    reg = MetricsRegistry()
    reg.histogram("w", mode="delta")          # registered, never observed
    pooled = reg.merged_quantiles("w", (0.5, 0.99))
    assert math.isnan(pooled[0.5]) and math.isnan(pooled[0.99])
    reg.histogram("w", mode="full").observe(7.0)
    pooled = reg.merged_quantiles("w", (0.5, 0.99))
    assert pooled[0.5] == 7.0 and pooled[0.99] == 7.0


def test_single_sample_quantiles():
    reg = MetricsRegistry()
    h = reg.histogram("w")
    h.observe(42.0)
    qs = h.quantiles((0.0, 0.5, 0.95, 0.99, 1.0))
    assert all(v == 42.0 for v in qs.values())


def test_counter_struct_label_collision():
    """Two shims over the same registry with identical labels share the
    underlying counters (keyed identity), while one distinct label splits
    them — so two services sharing one registry can never alias."""
    class S(CounterStruct):
        _FIELDS = ("a",)
        _PREFIX = "col_"

    reg = MetricsRegistry()
    s1 = S(reg, service="x")
    s2 = S(reg, service="x")
    s3 = S(reg, service="y")
    s1.a += 2
    assert s2.a == 2          # same (name, labels) -> same counter
    assert s3.a == 0
    s2.a += 1
    assert s1.a == 3


# ------------------------- OpenMetrics exposition ---------------------------

def test_openmetrics_render_and_validate():
    from repro.obs.expo import render_openmetrics, validate_openmetrics

    reg = MetricsRegistry()
    reg.counter("service_queries", service="local").inc(5)
    reg.gauge("adaptive_dirty_threshold", service="local", kind="bfs").set(
        0.25)
    h = reg.histogram("query_wall_us", service="local", kind="bfs",
                      mode="full")
    for v in (10.0, 20.0, 30.0):
        h.observe(v)
    text = render_openmetrics(reg, extra_counters={"trace_rotations": 2},
                              extra_gauges={"journal_depth": 7})
    assert validate_openmetrics(text) == []
    assert "# TYPE service_queries counter" in text
    assert 'service_queries_total{service="local"} 5' in text
    assert "# TYPE query_wall_us summary" in text
    assert 'quantile="0.5"' in text
    assert 'query_wall_us_count{kind="bfs",mode="full",service="local"} 3' \
        in text
    assert "trace_rotations_total 2" in text
    assert "journal_depth 7" in text
    assert text.rstrip().endswith("# EOF")


def test_openmetrics_label_escaping():
    """Label values containing ``"``, ``\\`` and newlines must round-trip
    through the escaper and still validate."""
    from repro.obs.expo import render_openmetrics, validate_openmetrics

    reg = MetricsRegistry()
    reg.counter("esc", what='say "hi"\nplease\\now').inc()
    text = render_openmetrics(reg)
    assert validate_openmetrics(text) == []
    assert r'what="say \"hi\"\nplease\\now"' in text


def test_openmetrics_validator_catches_breakage():
    from repro.obs.expo import validate_openmetrics

    good = ("# TYPE x counter\n# HELP x a counter.\nx_total 1\n# EOF\n")
    assert validate_openmetrics(good) == []
    # counter sample without _total
    bad = good.replace("x_total 1", "x 1")
    assert any("_total" in e for e in validate_openmetrics(bad))
    # missing EOF
    assert any("EOF" in e for e in validate_openmetrics(
        "# TYPE x counter\n# HELP x a.\nx_total 1\n"))
    # sample with no TYPE declaration
    assert any("TYPE" in e for e in validate_openmetrics(
        "y_total 1\n# EOF\n"))
    # non-numeric value
    assert any("non-numeric" in e for e in validate_openmetrics(
        "# TYPE x counter\n# HELP x a.\nx_total one\n# EOF\n"))
    # duplicate family
    assert any("twice" in e for e in validate_openmetrics(
        "# TYPE x counter\n# HELP x a.\n# TYPE x counter\nx_total 1\n"
        "# EOF\n"))


def test_expo_server_scrape_and_journal_depth(tmp_path):
    import urllib.request

    from repro.obs.expo import validate_openmetrics
    from repro.resil.journal import OpJournal

    jr = OpJournal(str(tmp_path / "wal.jsonl"))
    jr.append_op(0, ("pute", 0, 1, 1.0))
    jr.append_op(1, ("pute", 1, 2, 1.0))
    jr.commit_barrier(1, 2)
    jr.append_op(2, ("remv", 2))      # not yet barriered -> depth 1
    assert jr.depth == 1

    tel = Telemetry.make()
    tel.registry.counter("service_queries", service="local").inc(3)
    srv = tel.serve(port=0, journal=jr)
    try:
        body = urllib.request.urlopen(srv.url, timeout=10).read().decode()
    finally:
        srv.close()
        jr.close()
    assert validate_openmetrics(body) == []
    assert "journal_depth 1" in body
    assert "journal_ops_logged_total 3" in body
    assert 'service_queries_total{service="local"} 3' in body
    # a closed server refuses further scrapes (no dangling daemon port)
    tel.close()


def test_expo_cli_one_shot(tmp_path, capsys):
    """The offline twin: rebuild the exposition from trace JSONL and pass
    the same validator CI scrapes through."""
    from repro.obs import expo

    path = tmp_path / "t.jsonl"
    tr = Tracer(str(path))
    for mode, dev in (("full", 500.0), ("delta", 50.0), ("unchanged", 0.0)):
        with tr.span("query", service="local", kind="bfs", version=1,
                     mode=mode, coll_bytes=0, degraded=False,
                     device_us=dev, flops=1000.0):
            pass
    with tr.span("query", service="local", kind="bfs", error="Boom"):
        pass
    tr.close()
    assert expo.main([str(path), "--check"]) == 0
    out = capsys.readouterr().out
    # schema-2 records' device_us no longer rebuilds a histogram
    assert "query_wall_us" in out and "query_device_us" not in out
    assert 'service_errors_total{service="local"} 1' in out


# --------------------------- trace sink rotation ----------------------------

def test_trace_sink_rotation(tmp_path):
    """S1: a bounded JSONL sink rotates ``t.jsonl`` -> ``.1`` -> ``.2``
    (oldest dropped at ``keep``), counts rotations, keeps every record
    across the rotated set, and never interleaves a torn line."""
    import os

    path = tmp_path / "t.jsonl"
    tr = Tracer(str(path), max_bytes=2000, keep=2)
    n = 120
    for i in range(n):
        with tr.span("query", idx=i, pad="x" * 40):
            pass
    tr.close()
    assert tr.rotations > 1
    files = [str(path)] + [f"{path}.{i}" for i in (1, 2)]
    for f in files:
        assert os.path.exists(f), f
        assert os.path.getsize(f) <= 2000 + 200  # one record of slack
    assert not os.path.exists(f"{path}.3")       # keep=2 drops the rest
    survivors = []
    for f in files:
        for line in open(f):
            survivors.append(json.loads(line))   # no torn lines
    kept_idx = sorted(r["idx"] for r in survivors)
    # the newest records always survive; only the oldest rotated out
    assert kept_idx == list(range(n - len(kept_idx), n))
    # in-memory list saw everything regardless
    assert len(tr.records) == n and tr.sink_errors == 0


def test_trace_rotation_failure_keeps_stream(tmp_path, monkeypatch):
    """A failing rename must not kill the sink: the tracer reopens and
    keeps writing (best-effort telemetry, the WAL lesson)."""
    import os

    path = tmp_path / "t.jsonl"
    tr = Tracer(str(path), max_bytes=500, keep=2)

    real_replace = os.replace

    def boom(src, dst):
        raise OSError("disk says no")

    monkeypatch.setattr(os, "replace", boom)
    for i in range(40):
        with tr.span("query", idx=i, pad="y" * 40):
            pass
    assert tr.rotations == 0          # every rename failed...
    assert tr.sink_errors == 0        # ...yet no record was lost:
    lines = [json.loads(l) for l in open(path)]
    assert [r["idx"] for r in lines] == list(range(40))  # all appended
    monkeypatch.setattr(os, "replace", real_replace)
    with tr.span("query", idx=99):
        pass                          # oversized file: now rotates for real
    tr.close()
    assert tr.rotations == 1
    assert [json.loads(l)["idx"] for l in open(path)] == [99]
    assert json.loads(open(f"{path}.1").readlines()[-1])["idx"] == 39


# ------------------------------ report (PR 8) -------------------------------

def test_report_multi_file_and_json_format(tmp_path, capsys):
    """S2: rotated trace siblings merge (sorted by span id), ``--format
    json`` emits machine-readable rows, and the summary carries no
    device-time column (schema 3)."""
    p1, p2 = tmp_path / "t.jsonl.1", tmp_path / "t.jsonl"
    tr = Tracer(str(p1))
    common = dict(service="local", kind="bfs", version=1, coll_bytes=0,
                  degraded=False, flops=10.0)
    with tr.span("query", mode="full", **common):
        pass
    tr.close()
    tr2 = Tracer(str(p2))
    tr2._next_id = 50                  # rotated continuation: later ids
    with tr2.span("query", mode="delta", **common):
        pass
    tr2.close()

    records = report.load_many([str(p2), str(p1)])  # any order in
    assert [r["mode"] for r in records] == ["full", "delta"]  # id-sorted
    assert report.validate(records) == []
    rows = report.summarize(records)
    assert {r["mode"] for r in rows} == {"full", "delta"}
    assert all("device_p50_us" not in r for r in rows)
    assert "device_p50_us" not in report.render(rows)

    assert report.main([str(p2), str(p1), "--format", "json",
                        "--check"]) == 0
    out = capsys.readouterr().out
    data = json.loads(out[:out.rindex("]") + 1])
    assert len(data) == 2 and {r["mode"] for r in data} == {"full", "delta"}


def test_report_error_span_exemption():
    """Error-terminated query records stay exempt from the field check
    but are counted in the summary's errors column."""
    recs = [
        {"schema": TRACE_SCHEMA, "span": "query", "id": 0, "wall_us": 5.0,
         "service": "local", "kind": "bfs", "error": "Boom"},
        {"schema": TRACE_SCHEMA, "span": "query", "id": 1, "wall_us": 9.0,
         "service": "local", "kind": "bfs", "version": 1, "mode": "full",
         "coll_bytes": 0, "degraded": False, "flops": 2.0},
    ]
    assert report.validate(recs) == []
    rows = report.summarize(recs)
    err_row = next(r for r in rows if r["errors"])
    assert err_row["errors"] == 1


# ------------------------- adaptive thresholds ------------------------------

def _drive(ctl, kind, *, full_us, delta):
    """Feed synthetic observations: ``delta`` is (frac, wall_us) pairs."""
    for w in full_us:
        ctl.observe(kind, "full", w, None)
    for f, w in delta:
        ctl.observe(kind, "delta", w, f)


def test_adaptive_fits_crossover_and_steps():
    from repro.obs import AdaptiveThresholds

    ctl = AdaptiveThresholds(base=0.25, lo=0.02, hi=0.75, alpha=1.0,
                             period=8, min_full=2, min_delta=4,
                             probe_every=0)
    # delta cost = 100 + 1000*frac us; full cost = 600 us -> crossover 0.5
    _drive(ctl, "bfs", full_us=[600.0] * 3,
           delta=[(f, 100.0 + 1000.0 * f)
                  for f in (0.1, 0.2, 0.3, 0.4, 0.5)])
    thr = ctl.thresholds()["bfs"]
    assert abs(thr - 0.5) < 1e-6, thr
    assert ctl.adjustments == 1
    # other kinds untouched
    assert ctl.thresholds()["sssp"] == 0.25


def test_adaptive_clamps_and_damping():
    from repro.obs import AdaptiveThresholds

    # crossover far above hi -> clamp at hi even with alpha=1
    ctl = AdaptiveThresholds(base=0.25, lo=0.05, hi=0.4, alpha=1.0,
                             period=6, min_full=1, min_delta=3,
                             probe_every=0)
    _drive(ctl, "bfs", full_us=[10000.0] * 2,
           delta=[(f, 10.0 + 100.0 * f) for f in (0.1, 0.2, 0.3, 0.4)])
    assert ctl.thresholds()["bfs"] == 0.4
    # alpha damps the step: halfway to the target
    ctl2 = AdaptiveThresholds(base=0.25, lo=0.02, hi=0.75, alpha=0.5,
                              period=8, min_full=1, min_delta=4,
                              probe_every=0)
    _drive(ctl2, "bfs", full_us=[600.0] * 3,
           delta=[(f, 100.0 + 1000.0 * f)
                  for f in (0.1, 0.2, 0.3, 0.4, 0.5)])
    assert abs(ctl2.thresholds()["bfs"] - 0.375) < 1e-6  # 0.25 + 0.5*0.25


def test_adaptive_no_movement_without_signal():
    from repro.obs import AdaptiveThresholds

    ctl = AdaptiveThresholds(period=4, min_full=1, min_delta=2,
                             probe_every=0)
    # degenerate fit: every delta at the same fraction -> no movement
    _drive(ctl, "bfs", full_us=[500.0] * 2,
           delta=[(0.2, 100.0), (0.2, 120.0), (0.2, 90.0)])
    assert ctl.thresholds()["bfs"] == ctl.base["bfs"] and ctl.adjustments == 0
    # negative slope (delta CHEAPER when dirtier - noise): no movement
    _drive(ctl, "sssp", full_us=[500.0] * 2,
           delta=[(0.1, 300.0), (0.3, 200.0), (0.5, 100.0)])
    assert ctl.thresholds()["sssp"] == ctl.base["sssp"]
    # unchanged observations carry no crossover signal at all
    for _ in range(64):
        ctl.observe("bc", "unchanged", 1.0, None)
    assert ctl.adjustments == 0


def test_adaptive_probe_cadence():
    from repro.obs import AdaptiveThresholds

    ctl = AdaptiveThresholds(probe_every=4)
    got = [ctl.threshold("bfs") for _ in range(12)]
    assert got.count(0.0) == 3 and ctl.probes == 3
    assert all(t == ctl.base["bfs"] for t in got if t != 0.0)
    # probing disabled
    ctl2 = AdaptiveThresholds(probe_every=0)
    assert all(ctl2.threshold("bfs") != 0.0 for _ in range(20))
    # unknown kind: static base, never probed
    assert ctl.threshold("nope") == 0.25   # static fallback


def test_adaptive_emits_spans_and_gauges():
    from repro.obs import AdaptiveThresholds

    reg, tr = MetricsRegistry(), Tracer()
    ctl = AdaptiveThresholds(alpha=1.0, period=8, min_full=1, min_delta=4,
                             probe_every=0).bind(reg, tr, "local")
    assert reg.gauge("adaptive_dirty_threshold", service="local",
                     kind="bfs").value == ctl.base["bfs"]
    _drive(ctl, "bfs", full_us=[600.0] * 3,
           delta=[(f, 100.0 + 1000.0 * f)
                  for f in (0.1, 0.2, 0.3, 0.4, 0.5)])
    assert ctl.adjustments == 1
    assert reg.gauge("adaptive_dirty_threshold", service="local",
                     kind="bfs").value == ctl.thresholds()["bfs"]
    assert reg.counter("adaptive_adjustments", service="local",
                       kind="bfs").value == 1
    adj = [r for r in tr.records if r["span"] == "threshold_adjust"]
    assert len(adj) == 1
    r = adj[0]
    assert r["old"] == 0.25 and abs(r["new"] - 0.5) < 1e-6
    assert r["t_full_us"] == 600.0 and r["n_full"] == 3 and r["n_delta"] == 5
    assert not r["clamped"]


def test_adaptive_validation_and_telemetry_requirement():
    import pytest

    from repro.core import make_graph
    from repro.engine import GraphService
    from repro.obs import AdaptiveThresholds

    with pytest.raises(ValueError):
        AdaptiveThresholds(lo=0.5, base=0.25)   # lo > base
    with pytest.raises(ValueError):
        AdaptiveThresholds(alpha=0.0)
    with pytest.raises(ValueError):
        GraphService(make_graph(8, 16), adaptive=True)  # needs telemetry
