"""Async serving front end + the thread-safety bugfixes it exposed.

Four regression suites pin the bugfixes that concurrent serving forced:

  * **pin refcounting** — ``VersionRing`` pins are shared counters;
    ``PinnedSnapshot.release()`` is idempotent under concurrency and can
    never steal a pin another in-flight query holds;
  * **atomic stale serve** — ``_stale_reply`` pins the cached slot's
    version in the same critical section that checks residency, so a
    degraded reply never names a version that eviction already dropped;
  * **pin-aware cache pruning** — ``prune_result_cache`` exempts slots
    at pinned versions from both sweeps (an admitted query's rung must
    not be evicted out from under it);
  * **per-kind dirty thresholds** — BC's delta ladder crossover sits at
    a few percent dirty, far below BFS/SSSP's; the old shared 0.25
    default routed BC into guaranteed delta losses
    (``engine_bc_incr < 1x``) and the adaptive clamp couldn't reach the
    true crossover.

The front-end tests then cover the tentpole itself: batched compatible
queries bit-identical to sequential collects, delta-rung batching, the
dispatch-failure fallback, and per-request deadlines.  The randomized
concurrent differential (multi-client, mixed update+query) lives in
``test_stream_differential``.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import PUTE, PUTV, apply_ops, make_graph
from repro.core.queries import bc_dependencies, bfs, sssp
from repro.engine import GraphService
from repro.engine.incremental import results_equal
from repro.engine.service import (
    DEFAULT_DIRTY_THRESHOLDS,
    prune_result_cache,
    resolve_dirty_thresholds,
    _CacheSlot,
)
from repro.engine.version_ring import VersionRing
from repro.obs import AdaptiveThresholds, Telemetry
from repro.resil import (
    FaultPlan,
    P_SERVE_DISPATCH,
    ResiliencePolicy,
    fault_scope,
)
from repro.serve import AsyncGraphService, pad_pow2

VCAP, ECAP = 64, 256


def _seed_graph(rng, n=24, m=96):
    g = make_graph(VCAP, ECAP)
    ops = [(PUTV, i) for i in range(n)]
    for _ in range(m):
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        ops.append((PUTE, u, v, float(rng.integers(1, 9))))
    g, _ = apply_ops(g, ops)
    return g


def _path_graph(n=24):
    """0 -> 1 -> ... -> n-1: reachability from 0 is known exactly."""
    g = make_graph(VCAP, ECAP)
    ops = [(PUTV, i) for i in range(n)]
    ops += [(PUTE, i, i + 1, 1.0) for i in range(n - 1)]
    g, _ = apply_ops(g, ops)
    return g


def _states(g0, k):
    """k successive committed-looking states (one edge tweak each)."""
    out = []
    state = g0
    for i in range(k):
        state, _ = apply_ops(state, [(PUTE, i % 4, (i + 1) % 4,
                                      float(1 + i % 3))])
        out.append(state)
    return out


# --------------------- bugfix 1: pin refcounting ---------------------------

def test_pin_is_refcounted_and_handle_release_idempotent():
    rng = np.random.default_rng(0)
    g0 = _seed_graph(rng)
    ring = VersionRing(g0, depth=2)
    p1 = ring.pin()          # v0, count 1
    p2 = ring.pin(0)         # v0, count 2 — shared entry
    assert ring.pin_count(0) == 2
    for st in _states(g0, 3):
        ring.commit(st)      # v0 rotates out but is parked (pinned)
    assert ring.get_entry(0) is not None, "pinned version must survive"
    p1.release()
    p1.release()             # double release: idempotent no-op
    with p1:                 # context-manager exit: still a no-op
        pass
    assert ring.pin_count(0) == 1, "double release must not steal p2's pin"
    assert ring.get_entry(0) is not None
    p2.release()
    assert ring.pin_count(0) == 0
    assert ring.get_entry(0) is None, "last release evicts the parked entry"


def test_release_by_version_is_idempotent():
    rng = np.random.default_rng(1)
    ring = VersionRing(_seed_graph(rng), depth=2)
    ring.release(0)          # never pinned: no-op, no going negative
    ring.pin(0)
    ring.release(0)
    ring.release(0)          # extra: no-op
    assert ring.pin_count(0) == 0
    assert ring.pinned_versions() == []


def test_concurrent_pin_release_hammer():
    """Many threads pinning/releasing (incl. racing double-releases of
    shared handles) while commits rotate the window: counts must end at
    zero with nothing parked and no exceptions."""
    rng = np.random.default_rng(2)
    g0 = _seed_graph(rng)
    ring = VersionRing(g0, depth=3)
    states = _states(g0, 12)
    errs = []

    def pinner():
        try:
            for _ in range(50):
                p = ring.pin()
                time.sleep(0)
                # two racing releases of the SAME handle
                t = threading.Thread(target=p.release)
                t.start()
                p.release()
                t.join()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    threads = [threading.Thread(target=pinner) for _ in range(4)]
    for t in threads:
        t.start()
    for st in states:
        ring.commit(st)
        time.sleep(0.001)
    for t in threads:
        t.join()
    assert not errs, errs
    assert ring.pinned_versions() == []
    assert ring._parked == {}
    assert all(c > 0 for c in ring._pins.values())  # no zombie zeros


def test_try_pin_atomic_check_then_pin():
    rng = np.random.default_rng(3)
    g0 = _seed_graph(rng)
    ring = VersionRing(g0, depth=2)
    for st in _states(g0, 3):
        ring.commit(st)
    assert ring.try_pin(0) is None          # evicted: no handle
    with pytest.raises(KeyError):
        ring.pin(0)
    p = ring.try_pin()                      # latest
    assert p is not None and p.version == ring.latest.version
    p.release()


# --------------------- bugfix 2: atomic stale serve ------------------------

def test_stale_reply_none_once_version_evicted():
    rng = np.random.default_rng(4)
    svc = GraphService(_seed_graph(rng), ring_depth=2, batch_size=4,
                       policy=ResiliencePolicy())
    svc.query("bfs", 0)                     # slot cached at v0
    for _ in range(3):                      # rotate v0 out of the ring
        svc.submit_many([(PUTE, 1, 2, 1.0)] * 4)
        svc.flush()
    assert svc.ring.get_entry(0) is None
    assert svc._stale_reply("bfs", 0) is None, \
        "stale serve must refuse a version the ring no longer holds"
    svc.query("bfs", 0)                     # re-cache at the latest version
    reply = svc._stale_reply("bfs", 0)
    assert reply is not None and reply.degraded
    assert reply.stale_version == reply.version
    assert svc.ring.get_entry(reply.version) is not None


def test_stale_reply_vs_concurrent_eviction_hammer():
    """Commits rotating the ring race ``_stale_reply``: every reply that
    comes back must be the cached result at its claimed (then-resident)
    version — and the ring ends with no leaked pins."""
    rng = np.random.default_rng(5)
    svc = GraphService(_seed_graph(rng), ring_depth=2, batch_size=2,
                       policy=ResiliencePolicy())
    svc.query("bfs", 0)
    stop = threading.Event()
    errs = []

    def committer():
        try:
            while not stop.is_set():
                svc.submit_many([(PUTE, 1, 2, 1.0), (PUTE, 2, 3, 1.0)])
                svc.flush()
        except Exception as e:  # pragma: no cover
            errs.append(e)

    t = threading.Thread(target=committer)
    t.start()
    try:
        for i in range(200):
            reply = svc._stale_reply("bfs", 0)
            if reply is not None:
                assert reply.stale_version == reply.version
            if i % 50 == 0:     # refresh the slot so it stays servable
                svc.query("bfs", 0)
    finally:
        stop.set()
        t.join()
    assert not errs, errs
    assert svc.ring.pinned_versions() == []


# --------------------- bugfix 3: pin-aware pruning -------------------------

def test_prune_result_cache_exempts_pinned_versions():
    mk = lambda v: _CacheSlot(v, object())  # noqa: E731
    cache = {("bfs", i): mk(i) for i in range(5)}
    # floor sweep: version 1 is below floor but pinned -> survives
    prune_result_cache(cache, max_cached=3, floor=3, pinned=(1,))
    assert ("bfs", 1) in cache and ("bfs", 0) not in cache
    # eviction sweep never touches pinned slots even over budget
    cache = {("bfs", i): mk(5) if i < 3 else mk(i) for i in range(6)}
    prune_result_cache(cache, max_cached=2, floor=0, pinned=(5,))
    assert all(cache[k].version == 5 for k in cache), cache
    # everything pinned: cache may transiently exceed max_cached
    cache = {("bfs", i): mk(7) for i in range(4)}
    prune_result_cache(cache, max_cached=2, floor=0, pinned=(7,))
    assert len(cache) == 4


def test_service_prune_respects_admission_pins():
    rng = np.random.default_rng(6)
    svc = GraphService(_seed_graph(rng), batch_size=4, max_cached=2)
    pin = svc.ring.pin()                    # an admitted query's pin at v0
    for src in range(4):
        svc.query("bfs", src)               # all slots land at pinned v0
    assert len(svc._cache) == 4, "pinned-version slots must not be evicted"
    pin.release()
    svc.query("bfs", 5)                     # next store prunes normally
    assert len(svc._cache) <= 2


# --------------------- bugfix 4: per-kind thresholds -----------------------

def test_default_thresholds_are_per_kind():
    assert DEFAULT_DIRTY_THRESHOLDS["bc"] == 0.05
    assert DEFAULT_DIRTY_THRESHOLDS["bfs"] == 0.25
    kinds = ("bfs", "sssp", "bc")
    assert resolve_dirty_thresholds(None, kinds) == {
        "bfs": 0.25, "sssp": 0.25, "bc": 0.05}
    assert resolve_dirty_thresholds(0.1, kinds) == {
        k: 0.1 for k in kinds}
    assert resolve_dirty_thresholds({"bc": 0.02}, kinds) == {
        "bfs": 0.25, "sssp": 0.25, "bc": 0.02}
    rng = np.random.default_rng(7)
    svc = GraphService(_seed_graph(rng))
    assert svc.dirty_thresholds["bc"] == 0.05
    assert svc._threshold("bc") == 0.05 and svc._threshold("bfs") == 0.25
    svc2 = GraphService(_seed_graph(rng), dirty_threshold=0.3)
    assert svc2._threshold("bc") == 0.3


def test_bc_threshold_routes_marginal_fracs_to_full():
    """~8% dirty: below the old shared 0.25 (delta — a guaranteed loss
    for BC's full backward sweep), above the new 0.05 default (full)."""
    g0 = _path_graph()
    svc = GraphService(g0, batch_size=2)
    svc.query("bc", 0)
    # two NEW edges dirty two reached sources: 2/64 (vcap) ~ 3.1% -> delta
    svc.submit_many([(PUTE, 5, 7, 1.0), (PUTE, 9, 11, 1.0)])
    svc.flush()
    assert svc.query("bc", 0).mode == "delta"
    # eight new edges dirty 8 reached sources: 12.5% -> full under 0.05
    svc.submit_many([(PUTE, 2 * i, 2 * i + 3, 1.0) for i in range(8)])
    svc.flush()
    assert svc.query("bc", 0).mode == "full"


def test_adaptive_clamp_reaches_bc_crossover():
    ctl = AdaptiveThresholds()
    assert ctl.lo == 0.005, "clamp floor must reach BC's few-percent " \
        "crossover"
    ctl2 = AdaptiveThresholds(base={"bfs": 0.25, "sssp": 0.25, "bc": 0.05})
    assert ctl2.thresholds() == {"bfs": 0.25, "sssp": 0.25, "bc": 0.05}
    with pytest.raises(ValueError):
        AdaptiveThresholds(base={"bfs": 0.25, "sssp": 0.25, "bc": 0.001})


# ------------------------- async front end ---------------------------------

def test_pad_pow2():
    assert [pad_pow2(n) for n in (1, 2, 3, 4, 5, 8, 9)] == \
        [1, 2, 4, 4, 8, 8, 16]


def test_batched_full_dispatch_bit_identical():
    """A burst of same-kind queries at one version runs as ONE compiled
    batched dispatch whose per-lane answers are bit-equal to the
    sequential single-source collects."""
    rng = np.random.default_rng(8)
    g0 = _seed_graph(rng)
    tel = Telemetry(block=False)
    svc = GraphService(g0, batch_size=4, telemetry=tel)
    fresh = {"bfs": bfs, "sssp": sssp, "bc": bc_dependencies}
    with AsyncGraphService(svc, max_batch=16) as srv:
        for kind in ("bfs", "sssp", "bc"):
            futs = [(s, srv.query_async(kind, s)) for s in range(6)]
            for s, f in futs:
                reply = f.result(timeout=120)
                assert reply.version == 0 and reply.mode == "full"
                assert results_equal(reply.result, fresh[kind](g0, s)), \
                    (kind, s)
    assert srv.stats.batched_dispatches >= 1
    assert srv.stats.max_batch_seen >= 2
    sizes = [s for h in tel.registry.find("serve_batch_size")
             for s in h.samples]
    assert sizes and max(sizes) >= 2
    st = svc.stats
    assert st.unchanged + st.delta + st.full == st.queries == 18


def test_batched_delta_rung_bit_identical():
    """Cached priors + a small committed churn: the dispatcher batches
    the delta lanes (one batched delta kernel call) and each lane equals
    the sequential full collect on the new snapshot."""
    g0 = _path_graph()
    tel = Telemetry(block=False)
    svc = GraphService(g0, batch_size=2, telemetry=tel)
    srcs = (0, 1, 2)
    with AsyncGraphService(svc, max_batch=16) as srv:
        for s in srcs:                       # warm priors at v0
            srv.query("bfs", s, timeout=120)
        svc.submit_many([(PUTE, 5, 7, 1.0), (PUTE, 9, 11, 1.0)])
        svc.flush()
        g1 = svc.ring.latest.state
        futs = [(s, srv.query_async("bfs", s)) for s in srcs]
        replies = [(s, f.result(timeout=120)) for s, f in futs]
    for s, reply in replies:
        assert reply.version == 1
        assert reply.mode == "delta", (s, reply.mode)
        assert results_equal(reply.result, bfs(g1, s)), s
    delta_sizes = [s for h in tel.registry.find("serve_batch_size",
                                                rung="delta")
                   for s in h.samples]
    assert delta_sizes and max(delta_sizes) >= 2, \
        "delta lanes must share one compiled dispatch"


def test_dispatch_fault_degrades_to_per_request_path():
    """An injected fault at ``serve.dispatch`` poisons the batch, not the
    requests: each falls back to the sequential resilient path and every
    answer is still exact."""
    rng = np.random.default_rng(9)
    g0 = _seed_graph(rng)
    svc = GraphService(g0, batch_size=4, policy=ResiliencePolicy())
    plan = FaultPlan({P_SERVE_DISPATCH: [0]})
    with fault_scope(plan):
        with AsyncGraphService(svc, max_batch=16) as srv:
            futs = [(s, srv.query_async("bfs", s)) for s in range(4)]
            for s, f in futs:
                reply = f.result(timeout=120)
                assert not reply.degraded
                assert results_equal(reply.result, bfs(g0, s)), s
    assert plan.fired == 1, "the dispatcher must see the activating " \
        "thread's fault plan (context propagation)"
    assert srv.stats.fallbacks >= 1
    st = svc.stats
    assert st.unchanged + st.delta + st.full == st.queries


def test_deadline_expiry_stale_serves_or_raises():
    rng = np.random.default_rng(10)
    g0 = _seed_graph(rng)
    svc = GraphService(g0, batch_size=4,
                       policy=ResiliencePolicy(deadline_ms=60_000))
    with AsyncGraphService(svc, max_batch=8) as srv:
        srv.query("bfs", 0, timeout=120)    # cache a servable slot
        svc.policy = ResiliencePolicy(deadline_ms=0.0)   # expire instantly
        reply = srv.query("bfs", 0, timeout=120)
        assert reply.degraded and reply.mode == "degraded"
        assert svc.ring.get_entry(reply.version) is not None
        svc.policy = ResiliencePolicy(deadline_ms=0.0, allow_stale=False)
        with pytest.raises(TimeoutError):
            srv.query("bfs", 1, timeout=120)
    assert srv.stats.deadline_expired >= 2
    assert svc.stats.degraded == 1


def test_admission_contract():
    rng = np.random.default_rng(11)
    svc = GraphService(_seed_graph(rng), batch_size=4)
    srv = AsyncGraphService(svc)
    with pytest.raises(RuntimeError):
        srv.query_async("bfs", 0)           # not started
    with pytest.raises(ValueError):
        AsyncGraphService(svc, max_batch=0)
    with srv:
        with pytest.raises(KeyError):
            srv.query_async("nope", 0)
        with pytest.raises(ValueError):
            srv.query_async("bfs", 0, mode="cn")   # cn needs the sync path
        with pytest.raises(ValueError):
            srv.query_async("bfs", None)
        # out-of-range source: served, flagged not-ok (same as sync path)
        assert not bool(srv.query("bfs", VCAP + 7, timeout=120).result.ok)
        assert srv.query("bfs", 0, timeout=120).version == 0
    # stopped cleanly: no pins leaked, a second start works
    assert svc.ring.pinned_versions() == []
    with srv:
        assert srv.query("sssp", 1, timeout=120).version == 0


def test_updates_overlap_pinned_reads():
    """Commits land while older-version queries are still pinned and
    in flight: the ring parks pinned versions instead of blocking the
    writer, and both sides finish."""
    rng = np.random.default_rng(12)
    g0 = _seed_graph(rng)
    svc = GraphService(g0, ring_depth=2, batch_size=2)
    with AsyncGraphService(svc, max_batch=4) as srv:
        futs = [srv.query_async("bfs", s) for s in range(4)]
        for _ in range(4):                   # rotate the window twice over
            srv.submit_many([(PUTE, 1, 2, 1.0), (PUTE, 3, 4, 1.0)])
        srv.flush()
        assert svc.version == 4
        for f in futs:
            reply = f.result(timeout=120)
            assert reply.version in (0, 1, 2, 3, 4)
    assert svc.ring.pinned_versions() == []
