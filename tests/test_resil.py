"""Resilience subsystem: deterministic fault plans, the degrade ladder,
scheduler commit atomicity under failure, crash-consistent journal
recovery, and the post-fault invariant checker.

Every scenario is driven by an explicit :class:`repro.resil.FaultPlan`
schedule (or a seeded-random plan whose ``to_schedule()`` replay is
itself asserted), so each failure mode here is a regression test, not a
flake.  The randomized end-to-end chaos runs live in
``test_stream_differential``; this file pins the mechanisms one at a
time.
"""
import json

import numpy as np
import pytest

from repro.core import PUTE, PUTV, REMV, apply_ops, make_graph
from repro.engine import GraphService
from repro.resil import (
    FAULT_POINTS,
    P_CACHE_STORE,
    P_COLLECT_DELTA,
    P_COLLECT_DISPATCH,
    P_JOURNAL_BARRIER,
    P_JOURNAL_TORN,
    P_OBS_SINK,
    P_RING_EVICT,
    P_SCHED_APPLY,
    P_SCHED_RING_COMMIT,
    CircuitBreaker,
    FaultPlan,
    InjectedCrash,
    InjectedFault,
    JournalError,
    OpJournal,
    ResiliencePolicy,
    assert_service_ok,
    fault_scope,
    inject,
    journal_meta,
    read_journal,
    read_journal_versions,
    recover,
    segment_files,
    snapshot_dir,
    verify_service,
)

VCAP, ECAP = 64, 256


def _seed_graph(rng, n=24, m=96):
    g = make_graph(VCAP, ECAP)
    ops = [(PUTV, i) for i in range(n)]
    for _ in range(m):
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        ops.append((PUTE, u, v, float(rng.integers(1, 9))))
    g, _ = apply_ops(g, ops)
    return g


def _stream_ops(rng, n=24, count=40):
    ops = []
    for _ in range(count):
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        r = float(rng.random())
        if r < 0.1:
            ops.append((PUTV, u))
        elif r < 0.2:
            ops.append((REMV, u))
        else:
            ops.append((PUTE, u, v, float(rng.integers(1, 9))))
    return ops


def _assert_same_state(a, b):
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


# ------------------------------- fault plans --------------------------------

def test_inject_noop_without_plan():
    for p in FAULT_POINTS:
        inject(p)  # no active plan: must be free and silent


def test_scheduled_plan_fires_exact_hits():
    plan = FaultPlan({P_COLLECT_DISPATCH: [1, 3]})
    with fault_scope(plan):
        inject(P_COLLECT_DISPATCH)  # hit 0: pass
        with pytest.raises(InjectedFault) as ei:
            inject(P_COLLECT_DISPATCH)  # hit 1: fire
        assert ei.value.point == P_COLLECT_DISPATCH and ei.value.hit == 1
        inject(P_COLLECT_DISPATCH)  # hit 2: pass
        with pytest.raises(InjectedFault):
            inject(P_COLLECT_DISPATCH)  # hit 3: fire
        inject(P_SCHED_APPLY)  # other points untouched
    assert plan.fired == 2
    assert plan.to_schedule() == {P_COLLECT_DISPATCH: [1, 3]}


def test_crash_points_raise_base_exception():
    plan = FaultPlan({P_JOURNAL_BARRIER: [0]})
    with fault_scope(plan):
        with pytest.raises(InjectedCrash) as ei:
            inject(P_JOURNAL_BARRIER)
    assert not isinstance(ei.value, Exception)  # unswallowable by ladders


def test_random_plan_replays_identically():
    def drive(plan):
        fired = []
        with fault_scope(plan):
            for i in range(200):
                point = FAULT_POINTS[i % len(FAULT_POINTS)]
                try:
                    inject(point)
                except (InjectedFault, InjectedCrash):
                    fired.append((point, i))
        return fired

    p1 = FaultPlan(seed=5, rate=0.2)
    fired1 = drive(p1)
    assert fired1, "rate 0.2 over 200 hits must fire"
    # identical seeded plan -> identical decisions
    assert drive(FaultPlan(seed=5, rate=0.2)) == fired1
    # to_schedule() replays the exact pattern without the RNG
    assert drive(FaultPlan(p1.to_schedule())) == fired1


def test_max_faults_caps_firing_without_shifting_streams():
    p_uncapped = FaultPlan(seed=9, rate=0.5)
    p_capped = FaultPlan(seed=9, rate=0.5, max_faults=3)

    def decisions(plan):
        with fault_scope(plan):
            out = []
            for _ in range(100):
                try:
                    inject(P_COLLECT_DELTA)
                    out.append(False)
                except InjectedFault:
                    out.append(True)
        return out

    d_un, d_cap = decisions(p_uncapped), decisions(p_capped)
    assert p_capped.fired == 3
    assert d_cap == [d and i < [j for j, x in enumerate(d_un) if x][2] + 1
                     for i, d in enumerate(d_un)]


def test_fault_scope_nests_and_allows_none():
    with fault_scope(None):
        inject(P_SCHED_APPLY)
        plan = FaultPlan({P_SCHED_APPLY: [0]})
        with fault_scope(plan):
            with pytest.raises(InjectedFault):
                inject(P_SCHED_APPLY)
        inject(P_SCHED_APPLY)  # outer scope restored: no plan


# --------------------------------- policy -----------------------------------

def test_policy_validation_and_backoff():
    with pytest.raises(ValueError):
        ResiliencePolicy(max_retries=-1)
    with pytest.raises(ValueError):
        ResiliencePolicy(backoff_ms=-1.0)
    pol = ResiliencePolicy(backoff_ms=10.0, backoff_factor=2.0)
    assert pol.backoff_s(1) == 0.010 and pol.backoff_s(3) == 0.040
    assert ResiliencePolicy().backoff_s(5) == 0.0
    assert not ResiliencePolicy().deadline_exceeded(0.0)  # inf deadline
    assert ResiliencePolicy(deadline_ms=0.0).deadline_exceeded(0.0)


# ----------------------- service stats exception-safety ---------------------

def test_stats_conserved_when_collect_raises_no_policy():
    """Satellite regression: a raising collect must NOT count as a query —
    it lands in ``service_errors`` and conservation still holds."""
    rng = np.random.default_rng(0)
    svc = GraphService(_seed_graph(rng), batch_size=4)
    svc.query("bfs", 0)
    base = svc.stats.queries
    with fault_scope(FaultPlan({P_COLLECT_DISPATCH: [0]})):
        with pytest.raises(InjectedFault):
            svc.query("bfs", 1)
    st = svc.stats
    assert st.queries == base and st.errors == 1
    assert st.unchanged + st.delta + st.full == st.queries
    assert_service_ok(svc)
    # the service keeps serving afterwards
    assert svc.query("bfs", 1).version == svc.version


def test_cache_store_fault_preserves_old_slot():
    """A fault racing the result-cache store leaves the previously cached
    answer intact and servable (no torn slot)."""
    rng = np.random.default_rng(1)
    svc = GraphService(_seed_graph(rng), batch_size=4)
    r0 = svc.query("bfs", 0)
    slot_before = svc._cache[("bfs", 0)]
    svc.submit_many(_stream_ops(rng, count=8))
    svc.flush()
    with fault_scope(FaultPlan({P_CACHE_STORE: [0]})):
        with pytest.raises(InjectedFault):
            svc.query("bfs", 0)
    assert svc._cache[("bfs", 0)] is slot_before
    assert svc._cache[("bfs", 0)].version == r0.version
    assert_service_ok(svc)


# --------------------------- degrade ladder ---------------------------------

def test_retry_demotes_to_full_from_pinned_snapshot():
    """First attempt faults in the delta rung; the retry recomputes full
    and the answer matches a never-faulted twin bit-for-bit."""
    rng = np.random.default_rng(2)
    g0 = _seed_graph(rng)
    ops = _stream_ops(rng, count=8)
    pol = ResiliencePolicy(max_retries=1)
    svc = GraphService(g0, batch_size=4, policy=pol)
    twin = GraphService(g0, batch_size=4)
    for s in (svc, twin):
        s.query("bfs", 0)
        s.submit_many(ops)
        s.flush()
    with fault_scope(FaultPlan({P_COLLECT_DELTA: [0]})):
        reply = svc.query("bfs", 0)
    assert reply.mode == "full" and reply.retries == 1
    assert not reply.degraded
    assert svc.stats.retries == 1 and svc.stats.errors == 1
    _assert_same_state(reply.result, twin.query("bfs", 0).result)
    assert_service_ok(svc)


def test_ladder_exhausted_serves_stale_flagged_degraded():
    rng = np.random.default_rng(3)
    pol = ResiliencePolicy(max_retries=1)
    svc = GraphService(_seed_graph(rng), batch_size=4, policy=pol)
    r0 = svc.query("bfs", 0)
    svc.submit_many(_stream_ops(rng, count=8))
    svc.flush()
    assert svc.version > r0.version
    # attempt (delta rung) + retry (full rung) both fail
    with fault_scope(FaultPlan({P_COLLECT_DELTA: [0],
                                P_COLLECT_DISPATCH: [0]})):
        reply = svc.query("bfs", 0)
    assert reply.degraded and reply.mode == "degraded"
    assert reply.stale_version == reply.version == r0.version
    assert svc.ring.get_entry(reply.stale_version) is not None
    _assert_same_state(reply.result, r0.result)  # exact at its version
    assert svc.stats.degraded == 1 and svc.stats.errors == 2
    assert svc.stats.retries == 1
    assert_service_ok(svc)


def test_ladder_exhausted_nothing_cached_raises():
    """No resident cached answer -> a loud error, never a silent lie."""
    rng = np.random.default_rng(4)
    pol = ResiliencePolicy(max_retries=1)
    svc = GraphService(_seed_graph(rng), batch_size=4, policy=pol)
    with fault_scope(FaultPlan({P_COLLECT_DISPATCH: [0, 1]})):
        with pytest.raises(InjectedFault):
            svc.query("bfs", 0)
    assert svc.stats.degraded == 0 and svc.stats.errors == 2
    assert_service_ok(svc)


def test_allow_stale_off_reraises():
    rng = np.random.default_rng(5)
    pol = ResiliencePolicy(max_retries=0, allow_stale=False)
    svc = GraphService(_seed_graph(rng), batch_size=4, policy=pol)
    svc.query("bfs", 0)
    svc.submit_many(_stream_ops(rng, count=8))
    svc.flush()
    with fault_scope(FaultPlan({P_COLLECT_DELTA: [0]})):
        with pytest.raises(InjectedFault):
            svc.query("bfs", 0)
    assert svc.stats.degraded == 0
    assert_service_ok(svc)


def test_zero_deadline_skips_retries_straight_to_stale():
    rng = np.random.default_rng(6)
    pol = ResiliencePolicy(deadline_ms=0.0, max_retries=5)
    svc = GraphService(_seed_graph(rng), batch_size=4, policy=pol)
    svc.query("bfs", 0)
    with fault_scope(FaultPlan({P_COLLECT_DELTA: [0],
                                P_COLLECT_DISPATCH: [0]})):
        reply = svc.query("bfs", 0)
    assert reply.degraded
    assert svc.stats.retries == 0  # deadline spent before any retry
    assert_service_ok(svc)


# ------------------------ scheduler commit atomicity ------------------------

@pytest.mark.parametrize("point", [P_SCHED_APPLY, P_SCHED_RING_COMMIT])
def test_commit_atomic_under_fault(point):
    """A fault mid-commit (before apply, or between apply and the ring
    append) leaves ring latest AND pending log untouched; the retry then
    commits the identical prefix — bit-identical to a never-faulted twin."""
    rng = np.random.default_rng(7)
    g0 = _seed_graph(rng)
    ops = _stream_ops(rng, count=10)
    svc = GraphService(g0, batch_size=4)
    twin = GraphService(g0, batch_size=4)
    twin.submit_many(ops)
    twin.flush()

    with fault_scope(FaultPlan({point: [1]})):  # second batch's commit
        with pytest.raises(InjectedFault):
            svc.submit_many(ops)
        v = svc.version
        assert svc.scheduler.stats.commit_failures == 1
        # atomicity: the whole second chunk went back, in order (the
        # raising submit had already logged its own op)
        assert list(svc.scheduler._log) == ops[4:8]
        assert svc.scheduler.stats.ops_submitted == 8
        # resume the stream: the ops the raising submit_many never reached
        svc.submit_many(ops[8:])
        svc.flush()
    assert svc.version > v
    assert svc.scheduler.pending() == 0
    assert svc.version == twin.version
    _assert_same_state(svc.ring.latest.state, twin.ring.latest.state)
    assert_service_ok(svc)
    assert_service_ok(twin)


def test_ring_evict_fault_keeps_ring_consistent():
    """An eviction fault racing a commit aborts the commit atomically —
    the window, pins and latest stay exactly as before."""
    rng = np.random.default_rng(8)
    svc = GraphService(_seed_graph(rng), ring_depth=2, batch_size=4)
    svc.submit_many(_stream_ops(rng, count=16))
    svc.flush()  # window now full: next commit must evict
    v = svc.version
    window = list(svc.ring._window)
    with fault_scope(FaultPlan({P_RING_EVICT: [0]})):
        with pytest.raises(InjectedFault):
            svc.submit_many(_stream_ops(rng, count=4))
        assert svc.version == v and list(svc.ring._window) == window
        svc.flush()
    assert svc.version == v + 1
    assert_service_ok(svc)


# ------------------------------- journal ------------------------------------

def _journaled_service(tmp_path, g0, name="wal.jsonl", **kw):
    kw.setdefault("batch_size", 4)
    meta = journal_meta(g0, kw)
    journal = OpJournal(str(tmp_path / name), meta=meta)
    return GraphService(g0, journal=journal, **kw), journal


def test_journal_roundtrip_bit_identical(tmp_path):
    rng = np.random.default_rng(9)
    g0 = _seed_graph(rng)
    svc, journal = _journaled_service(tmp_path, g0)
    svc.submit_many(_stream_ops(rng, count=11))  # 2 commits + 3 pending
    assert svc.scheduler.pending() == 3
    journal.close()

    rec = recover(str(tmp_path / "wal.jsonl"), g0, batch_size=4)
    assert rec.version == svc.version
    _assert_same_state(rec.ring.latest.state, svc.ring.latest.state)
    assert rec.scheduler.pending() == 3
    assert list(rec.scheduler._log) == list(svc.scheduler._log)
    assert_service_ok(rec)
    # the recovered service keeps going exactly like the original
    # (whose WAL is closed, so detach it before driving it further)
    svc.scheduler.journal = None
    svc.flush()
    rec.flush()
    _assert_same_state(rec.ring.latest.state, svc.ring.latest.state)


def test_journal_recover_resumes_journaling(tmp_path):
    rng = np.random.default_rng(10)
    g0 = _seed_graph(rng)
    svc, journal = _journaled_service(tmp_path, g0)
    svc.submit_many(_stream_ops(rng, count=9))
    journal.close()
    rec = recover(str(tmp_path / "wal.jsonl"), g0, batch_size=4,
                  journal=OpJournal(str(tmp_path / "wal2.jsonl"),
                                    meta=journal_meta(g0, {"batch_size": 4})))
    rec.scheduler.journal.close()
    # the new journal recovers to the same place as the old one
    rec2 = recover(str(tmp_path / "wal2.jsonl"), g0, batch_size=4)
    assert rec2.version == rec.version == svc.version
    _assert_same_state(rec2.ring.latest.state, svc.ring.latest.state)


@pytest.mark.parametrize("crash_point", [P_JOURNAL_BARRIER, P_JOURNAL_TORN])
def test_crash_at_barrier_rolls_batch_back_atomically(tmp_path, crash_point):
    """Crash between the ring append and the barrier (or mid-barrier-write):
    recovery yields the ring WITHOUT the batch and the pending log WITH all
    of its ops — all-or-nothing, no torn prefix."""
    rng = np.random.default_rng(11)
    g0 = _seed_graph(rng)
    svc, journal = _journaled_service(tmp_path, g0)
    first = _stream_ops(rng, count=4)
    svc.submit_many(first)  # one clean committed batch (plan not active)
    v_before = svc.version
    doomed = _stream_ops(rng, count=4)
    # inside the scope the doomed batch's barrier is the first hit of
    # either crash point
    with fault_scope(FaultPlan({crash_point: [0]})):
        with pytest.raises(InjectedCrash):
            svc.submit_many(doomed)
    journal.close()

    rec = recover(str(tmp_path / "wal.jsonl"), g0, batch_size=4)
    assert rec.version == v_before  # the doomed batch rolled back...
    assert rec.scheduler.pending() == len(doomed)  # ...into pending, whole
    assert list(rec.scheduler._log) == [tuple(op) for op in doomed]
    assert_service_ok(rec)
    # replaying the pending ops reconverges with the pre-crash intent
    rec.flush()
    twin = GraphService(g0, batch_size=4)
    twin.submit_many(first)
    twin.submit_many(doomed)
    twin.flush()
    _assert_same_state(rec.ring.latest.state, twin.ring.latest.state)


def test_torn_final_line_tolerated_interior_raises(tmp_path):
    path = tmp_path / "wal.jsonl"
    g0 = _seed_graph(np.random.default_rng(12))
    svc, journal = _journaled_service(tmp_path, g0)
    svc.submit_many(_stream_ops(np.random.default_rng(12), count=6))
    journal.close()
    raw = path.read_text()
    # torn FINAL line: parse up to the last complete record
    path.write_text(raw + '{"t": "op", "se')
    meta, batches, pending = read_journal(str(path))
    assert meta["batch_size"] == 4 and len(batches) == 1
    assert recover(str(path), g0, batch_size=4).version == svc.version
    # torn INTERIOR line: real corruption, loud failure
    lines = raw.strip().split("\n")
    lines[2] = lines[2][: len(lines[2]) // 2]
    path.write_text("\n".join(lines) + "\n")
    with pytest.raises(JournalError):
        read_journal(str(path))


def test_journal_meta_mismatch_and_overcounting_barrier(tmp_path):
    path = tmp_path / "wal.jsonl"
    g0 = _seed_graph(np.random.default_rng(13))
    svc, journal = _journaled_service(tmp_path, g0)
    svc.submit_many(_stream_ops(np.random.default_rng(13), count=4))
    journal.close()
    with pytest.raises(JournalError, match="batch_size"):
        recover(str(path), g0, batch_size=8)
    with open(path, "a") as f:  # a barrier claiming ops never journaled
        f.write(json.dumps({"t": "commit", "version": 99, "ops": 7}) + "\n")
    with pytest.raises(JournalError, match="barrier covers"):
        read_journal(str(path))


# ------------------------------ invariants ----------------------------------

def test_verify_service_flags_planted_violations():
    rng = np.random.default_rng(14)
    svc = GraphService(_seed_graph(rng), batch_size=4)
    svc.query("bfs", 0)
    assert verify_service(svc) == []
    svc.stats.queries += 1  # break mode conservation
    assert any("conservation" in p for p in verify_service(svc))
    svc.stats.queries -= 1
    svc.scheduler.stats.ops_submitted += 2  # break the op ledger
    assert any("ledger" in p for p in verify_service(svc))
    svc.scheduler.stats.ops_submitted -= 2
    assert verify_service(svc) == []
    with pytest.raises(AssertionError):
        svc._cache[("bfs", 0)].version = svc.version + 5
        assert_service_ok(svc)


# ------------------------- telemetry sink faults ----------------------------

def test_tracer_sink_fault_never_raises(tmp_path):
    from repro.obs import Tracer
    path = tmp_path / "t.jsonl"
    tr = Tracer(str(path))
    with fault_scope(FaultPlan({P_OBS_SINK: [1]})):
        with tr.span("query", kind="bfs"):
            pass
        with tr.span("query", kind="sssp"):  # sink write faults; span OK
            pass
        with tr.span("query", kind="bc"):
            pass
    tr.close()
    assert tr.sink_errors == 1
    assert [r["kind"] for r in tr.records] == ["bfs", "sssp", "bc"]
    on_disk = [json.loads(x) for x in path.read_text().splitlines()]
    assert [r["kind"] for r in on_disk] == ["bfs", "bc"]  # one line lost


def test_service_stream_with_failing_sink_stays_correct(tmp_path):
    """Telemetry IO faults mid-stream: queries keep answering, counters
    keep conserving, only sink lines are lost."""
    from repro.obs import Telemetry
    rng = np.random.default_rng(15)
    tel = Telemetry.make(str(tmp_path / "t.jsonl"))
    svc = GraphService(_seed_graph(rng), batch_size=4, telemetry=tel)
    with fault_scope(FaultPlan(seed=1, rate=0.3,
                               points=(P_OBS_SINK,))):
        for step in range(4):
            svc.submit_many(_stream_ops(rng, count=6))
            svc.flush()
            for kind in ("bfs", "sssp", "bc"):
                svc.query(kind, 0)
    assert tel.tracer.sink_errors > 0
    assert len([r for r in tel.tracer.records if r["span"] == "query"]) == 12
    assert svc.stats.queries == 12
    assert_service_ok(svc)
    tel.close()


# --------------------- segment rotation + compaction ------------------------

def _segmented_service(tmp_path, g0, *, name="wal.jsonl", segment_bytes=700,
                       **kw):
    kw.setdefault("batch_size", 4)
    meta = journal_meta(g0, kw)
    journal = OpJournal(str(tmp_path / name), meta=meta,
                        segment_bytes=segment_bytes)
    return GraphService(g0, journal=journal, **kw), journal


def test_segment_rotation_replays_bit_identical(tmp_path):
    """Rotation seals segments only at barrier boundaries; the multi-file
    reader stitches them back into the exact batch sequence."""
    rng = np.random.default_rng(21)
    g0 = _seed_graph(rng)
    svc, journal = _segmented_service(tmp_path, g0)
    svc.submit_many(_stream_ops(rng, count=42))
    svc.flush()
    assert journal.rotations >= 3
    assert len(segment_files(journal.path)) == journal.rotations
    meta, vbatches, pending = read_journal_versions(journal.path)
    assert [v for v, _ in vbatches] == list(
        range(1, svc.ring.latest.version + 1))
    assert pending == []
    journal.close()
    rec = recover(journal.path, g0, batch_size=4)
    assert rec.ring.latest.version == svc.ring.latest.version
    _assert_same_state(svc.ring.latest.state, rec.ring.latest.state)
    assert_service_ok(rec)


def test_compaction_bounds_disk_and_recovers_without_initial_state(tmp_path):
    """>= 3 sealed segments, then compact: every covered segment is
    deleted, on-disk WAL = snapshot + (fresh) active file, and recovery
    restores from the snapshot alone — no initial state, bit-identical
    answers."""
    rng = np.random.default_rng(22)
    g0 = _seed_graph(rng)
    svc, journal = _segmented_service(tmp_path, g0)
    svc.submit_many(_stream_ops(rng, count=44))
    svc.flush()
    sealed = len(segment_files(journal.path))
    assert sealed >= 3
    report = svc.compact_wal()
    # compact seals the active history first, so every segment is covered
    assert report["segments_dropped"] == sealed + 1
    assert report["segments_kept"] == 0
    assert report["snapshot_bytes"] > 0
    assert segment_files(journal.path) == []
    # bounded disk: exactly the active WAL (one meta header) + snapshot
    assert sorted(p.name for p in tmp_path.iterdir()) == [
        "wal.jsonl", "wal.jsonl.ckpt"]
    meta, batches, pending = read_journal_versions(journal.path)
    assert batches == [] and pending == []

    expected = {k: svc.query(k, 0) for k in ("bfs", "sssp", "bc")}
    journal.close()
    rec = recover(journal.path, batch_size=4)  # no initial_state
    assert rec.ring.latest.version == svc.ring.latest.version
    _assert_same_state(svc.ring.latest.state, rec.ring.latest.state)
    for k, want in expected.items():
        got = rec.query(k, 0)
        assert got.version == want.version
        for x, y in zip(want.result, got.result):
            assert np.array_equal(np.asarray(x), np.asarray(y))
    assert_service_ok(rec)


def test_recovery_replays_tail_after_compaction(tmp_path):
    """Post-compaction commits land in fresh segments; recovery is
    snapshot + tail replay (never the full history)."""
    rng = np.random.default_rng(23)
    g0 = _seed_graph(rng)
    svc, journal = _segmented_service(tmp_path, g0)
    svc.submit_many(_stream_ops(rng, count=24))
    svc.flush()
    svc.compact_wal()
    snap_version = svc.ring.latest.version
    svc.submit_many(_stream_ops(rng, count=14))  # 3 commits + 2 pending
    svc.flush()
    journal.close()
    rec = recover(journal.path, batch_size=4)
    assert rec.ring.latest.version == svc.ring.latest.version
    _assert_same_state(svc.ring.latest.state, rec.ring.latest.state)
    # the rebased ring starts at the snapshot version: elided history
    # is truly elided, not replayed
    assert rec.ring.oldest_version >= snap_version
    assert_service_ok(rec)


def test_compacted_journal_recovers_from_any_crash_point(tmp_path):
    """Chaos stream with auto-compaction: crash at EVERY barrier in turn,
    recover, and the ring latest must equal the uninterrupted oracle's
    state at that version — all-or-nothing batches, snapshot + tail."""
    rng = np.random.default_rng(24)
    g0 = _seed_graph(rng)
    ops = _stream_ops(rng, count=36)  # 9 full batches at batch_size=4
    twin, tj = _segmented_service(tmp_path, g0, name="twin.jsonl")
    twin.submit_many(ops)
    twin.flush()
    n_barriers = twin.scheduler.stats.batches_committed
    tj.close()
    _, twin_batches, _ = read_journal(str(tmp_path / "twin.jsonl"))

    for hit in range(n_barriers):
        name = f"wal{hit}.jsonl"
        svc, journal = _segmented_service(tmp_path, g0, name=name,
                                          compact_every=3)
        with fault_scope(FaultPlan({P_JOURNAL_BARRIER: [hit]})):
            with pytest.raises(InjectedCrash):
                svc.submit_many(ops)
                svc.flush()
        journal.close()
        rec = recover(str(tmp_path / name), g0, batch_size=4)
        assert rec.ring.latest.version == hit
        expected = g0
        for chunk in twin_batches[:hit]:
            expected, _ = apply_ops(expected, list(chunk), batch_size=4)
        _assert_same_state(expected, rec.ring.latest.state)
        # the crashed batch's ops are back in the pending log, uncommitted
        assert rec.scheduler.pending() == 4
        assert_service_ok(rec)


def test_recover_detects_missing_segment(tmp_path):
    """A deleted (uncovered) segment is a replay gap, not silent skew."""
    rng = np.random.default_rng(25)
    g0 = _seed_graph(rng)
    svc, journal = _segmented_service(tmp_path, g0)
    svc.submit_many(_stream_ops(rng, count=40))
    svc.flush()
    segs = segment_files(journal.path)
    assert len(segs) >= 3
    journal.close()
    (tmp_path / segs[1][1].split("/")[-1]).unlink()  # drop a middle segment
    with pytest.raises(JournalError, match="replay gap"):
        recover(journal.path, g0, batch_size=4)


def test_adaptive_thresholds_ride_the_snapshot(tmp_path):
    """Learned dirty thresholds persist through compact + recover: the
    recovered service resumes tuned, not at cold defaults."""
    from repro.obs import Telemetry
    rng = np.random.default_rng(26)
    g0 = _seed_graph(rng)
    tel = Telemetry.make(str(tmp_path / "t.jsonl"), hlo=False)
    kw = dict(batch_size=4)
    journal = OpJournal(str(tmp_path / "wal.jsonl"),
                        meta=journal_meta(g0, kw))
    svc = GraphService(g0, journal=journal, telemetry=tel, adaptive=True,
                       **kw)
    svc.submit_many(_stream_ops(rng, count=12))
    svc.flush()
    learned = {"bfs": 0.11, "sssp": 0.62, "bc": 0.33}
    svc.adaptive.restore(learned)
    report = svc.compact_wal()
    assert report["version"] == svc.ring.latest.version
    journal.close()

    tel2 = Telemetry.make(str(tmp_path / "t2.jsonl"), hlo=False)
    rec = recover(str(tmp_path / "wal.jsonl"), batch_size=4,
                  telemetry=tel2, adaptive=True)
    got = rec.adaptive.thresholds()
    for k, v in learned.items():
        assert got[k] == pytest.approx(v)
    # the op ledger rode along too: conservation invariants hold
    assert_service_ok(rec)
    tel.close()
    tel2.close()


def test_recover_resumed_journal_is_self_contained(tmp_path):
    """recover(journal=new) after compaction re-compacts the restored
    base into the new journal, so the new WAL alone can recover."""
    rng = np.random.default_rng(27)
    g0 = _seed_graph(rng)
    svc, journal = _segmented_service(tmp_path, g0)
    svc.submit_many(_stream_ops(rng, count=24))
    svc.flush()
    svc.compact_wal()
    journal.close()
    kw = dict(batch_size=4)
    rec = recover(journal.path, batch_size=4,
                  journal=OpJournal(str(tmp_path / "wal2.jsonl"),
                                    meta=journal_meta(g0, kw)))
    rec.submit_many(_stream_ops(rng, count=8))
    rec.flush()
    rec.scheduler.journal.close()
    rec2 = recover(str(tmp_path / "wal2.jsonl"), batch_size=4)
    assert rec2.ring.latest.version == rec.ring.latest.version
    _assert_same_state(rec.ring.latest.state, rec2.ring.latest.state)
    assert_service_ok(rec2)


# --------------------------- circuit breaker --------------------------------

def test_breaker_state_machine():
    br = CircuitBreaker(fail_threshold=2, cooldown=3, probes=2)
    assert br.state("bfs") == br.CLOSED
    assert br.allow_delta("bfs")
    br.record_failure("bfs")
    br.record_success("bfs")  # success resets the consecutive count
    br.record_failure("bfs")
    assert br.state("bfs") == br.CLOSED
    br.record_failure("bfs")
    assert br.state("bfs") == br.OPEN and br.trips == 1
    assert br.state("sssp") == br.CLOSED  # fault domains are per kind
    # cooldown: two denials, the third consult is the half-open probe
    assert not br.allow_delta("bfs")
    assert not br.allow_delta("bfs")
    assert br.allow_delta("bfs")
    assert br.state("bfs") == br.HALF_OPEN
    br.record_success("bfs")  # probe 1 of 2
    assert br.state("bfs") == br.HALF_OPEN
    br.record_success("bfs")
    assert br.state("bfs") == br.CLOSED and br.restores == 1
    # a half-open probe failure re-opens with a fresh cooldown
    br.record_failure("bfs")
    br.record_failure("bfs")
    assert br.state("bfs") == br.OPEN
    for _ in range(3):
        br.allow_delta("bfs")
    assert br.state("bfs") == br.HALF_OPEN
    br.record_failure("bfs")
    assert br.state("bfs") == br.OPEN and br.trips == 3


def _churn(rng, *svcs, n=24):
    """One random edge insert, applied identically to every service."""
    u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
    op = (PUTE, u, v, float(rng.integers(1, 9)))
    for svc in svcs:
        svc.submit(op)
        svc.flush()


def test_breaker_trips_pins_full_and_half_open_restores(tmp_path):
    """Acceptance: forced consecutive delta failures trip the breaker —
    queries keep succeeding via full with zero wrong answers, a
    ladder_pinned span + breaker_open gauge are emitted — and half-open
    probes restore delta serving once the fault plan clears."""
    from repro.obs import Telemetry
    rng = np.random.default_rng(31)
    g0 = _seed_graph(rng)
    tel = Telemetry.make(str(tmp_path / "t.jsonl"), hlo=False)
    oracle = GraphService(g0, batch_size=4)  # fault-free twin
    svc = GraphService(g0, batch_size=4, telemetry=tel,
                       policy=ResiliencePolicy(max_retries=1),
                       breaker=CircuitBreaker(fail_threshold=3, cooldown=2,
                                              probes=1))
    ops = _stream_ops(rng, count=8)
    for s in (svc, oracle):
        s.submit_many(ops)
        s.flush()
        s.query("bfs", 0)  # seed the delta path's cached prior

    def check(reply):
        with fault_scope(FaultPlan({})):  # shield the oracle from the plan
            want = oracle.query("bfs", 0)
        assert reply.version == want.version and not reply.degraded
        assert np.array_equal(np.asarray(reply.result.dist),
                              np.asarray(want.result.dist))

    with fault_scope(FaultPlan({P_COLLECT_DELTA: list(range(64))})):
        for i in range(3):  # every delta attempt fails -> retried as full
            _churn(rng, svc, oracle)
            reply = svc.query("bfs", 0)
            assert reply.retries == 1
            check(reply)
        assert svc.breaker.state("bfs") == "open"
        # tripped: the delta point is still armed, but the quarantined
        # ladder never reaches it — clean full answers, zero retries
        _churn(rng, svc, oracle)
        reply = svc.query("bfs", 0)
        assert reply.mode == "full" and reply.retries == 0
        check(reply)
    # plan cleared: next consult exhausts the cooldown and probes
    _churn(rng, svc, oracle)
    reply = svc.query("bfs", 0)
    assert reply.mode == "delta" and svc.breaker.state("bfs") == "closed"
    check(reply)
    assert svc.breaker.trips == 1 and svc.breaker.restores == 1
    assert svc.stats.errors == 3 and svc.stats.degraded == 0
    assert_service_ok(svc)
    tel.close()
    recs = [json.loads(x) for x in
            (tmp_path / "t.jsonl").read_text().splitlines()]
    pinned = [r for r in recs if r.get("span") == "ladder_pinned"]
    restored = [r for r in recs if r.get("span") == "ladder_restored"]
    assert len(pinned) == 1 and pinned[0]["kind"] == "bfs"
    assert len(restored) == 1
    open_gauges = tel.registry.find("breaker_open", kind="bfs")
    assert open_gauges and open_gauges[0].value == 0.0  # restored: back to 0


def test_breaker_quarantines_sharded_delta_path(tmp_path):
    """Sharded service: a tripped breaker pins the ladder at full; the
    full-path answers stay bit-identical to the local oracle."""
    from repro.shard import ShardedGraphService, as_graph_mesh
    rng = np.random.default_rng(32)
    g0 = _seed_graph(rng)
    oracle = GraphService(g0, batch_size=4)
    svc = ShardedGraphService(
        g0, as_graph_mesh(), batch_size=4, src_chunk=2,
        policy=ResiliencePolicy(max_retries=1),
        breaker=CircuitBreaker(fail_threshold=2, cooldown=2, probes=1))
    ops = _stream_ops(rng, count=8)
    for s in (svc, oracle):
        s.submit_many(ops)
        s.flush()
        s.query("bfs", [0] if s is svc else 0)
    with fault_scope(FaultPlan({P_COLLECT_DELTA: list(range(64))})):
        for i in range(2):
            _churn(rng, svc, oracle)
            reply = svc.query("bfs", [0])
            assert reply.retries == 1
    assert svc.breaker.state("bfs") == "open"
    _churn(rng, svc, oracle)
    reply = svc.query("bfs", [0])
    want = oracle.query("bfs", 0)
    assert reply.mode == "full" and reply.retries == 0
    assert np.array_equal(np.asarray(reply.result.dist[0]),
                          np.asarray(want.result.dist))
    assert_service_ok(svc)


def test_verify_service_flags_journal_ledger_skew(tmp_path):
    rng = np.random.default_rng(33)
    g0 = _seed_graph(rng)
    svc, journal = _journaled_service(tmp_path, g0)
    svc.submit_many(_stream_ops(rng, count=6))
    assert verify_service(svc) == []
    journal.ops_logged += 2  # fake write-ahead records with no pending ops
    problems = verify_service(svc)
    assert any("journal depth" in p for p in problems)
