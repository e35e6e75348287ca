"""Engine subsystem: delta-query equivalence, version-ring semantics,
scheduler order/coalescing guarantees, and the GraphService front end."""
import numpy as np
import pytest

from repro.core import (
    PUTE, PUTV, REME, REMV,
    apply_ops, dirty_vertices, make_graph, queries,
)
from repro.core.graph_state import NOKEY, live_edge_mask
from repro.core.queries import bc_level_cut
from repro.engine import (
    GraphService,
    StreamScheduler,
    VersionRing,
    incremental_bc,
    incremental_bfs,
    incremental_sssp,
    validate_incremental,
)

VCAP, ECAP = 96, 512


def _seed_graph(rng, n=VCAP, m=4 * VCAP):
    g = make_graph(VCAP, ECAP)
    ops = [(PUTV, i) for i in range(n)]
    for _ in range(m):
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        ops.append((PUTE, u, v, float(rng.integers(1, 9))))
    g, _ = apply_ops(g, ops)
    return g


def _random_commit(rng, n=VCAP, n_ops=8, vertex_churn=True):
    """One commit's worth of randomized inserts/deletes."""
    ops = []
    for _ in range(n_ops):
        r = rng.random()
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        if vertex_churn and r < 0.06:
            ops.append((REMV, u))
        elif vertex_churn and r < 0.12:
            ops.append((PUTV, u))
        elif r < 0.6:
            ops.append((PUTE, u, v, float(rng.integers(1, 9))))
        else:
            ops.append((REME, u, v))
    return ops


def _edge_set(state):
    live = np.asarray(live_edge_mask(state))
    src = np.asarray(state.esrc)[live]
    dst = np.asarray(state.edst)[live]
    w = np.asarray(state.ew)[live]
    return {(int(u), int(v), float(x)) for u, v, x in zip(src, dst, w)}


def _assert_bit_identical(res, fresh):
    for a, b in zip(res, fresh):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# ------------------------- incremental equivalence -------------------------

@pytest.mark.parametrize("kind,incr,full", [
    ("bfs", incremental_bfs, queries.bfs),
    ("sssp", incremental_sssp, queries.sssp),
    ("bc", incremental_bc, queries.bc_dependencies),
])
def test_incremental_matches_fresh_over_randomized_stream(kind, incr, full):
    """>= 20 randomized update/query interleavings, bit-identical results."""
    rng = np.random.default_rng(7)
    state = _seed_graph(rng)
    src = 0
    prior, stats = incr(state, None, None, src)
    assert stats.mode == "full"
    _assert_bit_identical(prior, full(state, src))
    modes = {"unchanged": 0, "delta": 0, "full": 0}
    for _ in range(24):
        new_state, _ = apply_ops(state, _random_commit(rng))
        dirty = dirty_vertices(state, new_state)
        res, stats = incr(new_state, prior, dirty, src)
        modes[stats.mode] += 1
        _assert_bit_identical(res, full(new_state, src))
        assert validate_incremental(new_state, src, res, kind)
        state, prior = new_state, res
    assert modes["delta"] > 0  # the delta path actually exercised


def test_incremental_unchanged_shortcut():
    rng = np.random.default_rng(1)
    state = _seed_graph(rng)
    prior, _ = incremental_bfs(state, None, None, 0)
    res, stats = incremental_bfs(
        state, prior, np.zeros(state.vcap, bool), 0)
    assert stats.mode == "unchanged" and res is prior


def test_incremental_threshold_falls_back_to_full():
    rng = np.random.default_rng(2)
    state = _seed_graph(rng)
    prior, _ = incremental_bfs(state, None, None, 0)
    all_dirty = np.ones(state.vcap, bool)
    res, stats = incremental_bfs(state, prior, all_dirty, 0,
                                 dirty_threshold=0.25)
    assert stats.mode == "full"
    _assert_bit_identical(res, queries.bfs(state, 0))


def test_incremental_unchanged_beats_threshold():
    """Heavy churn entirely outside the reached region: the cached answer
    is still valid, however large the dirty set."""
    g = make_graph(64, 64)
    g, _ = apply_ops(g, [(PUTV, i) for i in range(64)] + [(PUTE, 0, 1, 1.0)])
    prior, _ = incremental_bfs(g, None, None, 0)  # reaches only {0, 1}
    dirty = np.arange(64) >= 2  # 97% dirty, none of it reached
    res, stats = incremental_bfs(g, prior, dirty, 0, dirty_threshold=0.25)
    assert stats.mode == "unchanged" and res is prior


def test_incremental_sssp_zero_weight_parent_cycle():
    """Zero-weight tight edges can make the prior parent 'tree' cyclic;
    poison must still reach the cycle when its feeding edge is removed."""
    g = make_graph(8, 16)
    g, _ = apply_ops(g, [(PUTV, 0), (PUTV, 1), (PUTV, 2),
                         (PUTE, 2, 0, 1.0),
                         (PUTE, 0, 1, 0.0), (PUTE, 1, 0, 0.0)])
    prior, _ = incremental_sssp(g, None, None, 2)
    par = np.asarray(prior.parent)
    assert par[0] == 1 and par[1] == 0  # the parent cycle actually formed
    g2, _ = apply_ops(g, [(REME, 2, 0)])  # cut the cycle's only feed
    res, stats = incremental_sssp(g2, prior, dirty_vertices(g, g2), 2)
    assert stats.mode == "delta"
    _assert_bit_identical(res, queries.sssp(g2, 2))  # 0 and 1 unreachable


def _chain_graph(depth=8, width=2):
    """Layered DAG: vertex l*width+j sits at BFS level l from source 0."""
    n = depth * width
    ops = [(PUTV, i) for i in range(n)]
    ops += [(PUTE, 0, j, 1.0) for j in range(1, width)]  # level-0 clique seed
    for l in range(depth - 1):
        for j in range(width):
            for k in range(width):
                ops.append((PUTE, l * width + j, (l + 1) * width + k, 1.0))
    g = make_graph(n, 4 * n * width)
    g, _ = apply_ops(g, ops)
    return g, n


def test_bc_level_cut_semantics():
    """Edge churn at level l cuts at l+1; a death at level l cuts at l;
    untouched sources cut past every level."""
    g, n = _chain_graph(depth=6, width=2)
    prior = queries.bc_dependencies(g, 0)
    lvl = np.asarray(prior.level)
    deep = int(np.flatnonzero(lvl == 4)[0])
    dirty = np.zeros(n, bool)
    dirty[deep] = True
    cut = int(bc_level_cut(prior.level, dirty, g.alive))
    assert cut == 5  # out-edge churn at level 4 can only disturb level >= 5
    g2, _ = apply_ops(g, [(REMV, deep)])
    cut2 = int(bc_level_cut(prior.level, dirty_vertices(g, g2), g2.alive))
    assert cut2 == 4  # the vertex itself died: its own level is suspect
    assert int(bc_level_cut(prior.level, np.zeros(n, bool), g.alive)) > 5


def test_incremental_bc_deep_cut_is_delta_and_exact():
    """Churn confined below the median level takes the delta path and is
    bit-identical to a fresh bc_dependencies (level/sigma/delta all)."""
    g, n = _chain_graph(depth=8, width=2)
    prior, st = incremental_bc(g, None, None, 0)
    assert st.mode == "full"
    deep = int(np.flatnonzero(np.asarray(prior.level) == 6)[0])
    g2, _ = apply_ops(g, [(REME, deep, int(np.flatnonzero(
        np.asarray(prior.level) == 7)[0]))])
    res, st = incremental_bc(g2, prior, dirty_vertices(g, g2), 0)
    assert st.mode == "delta"
    _assert_bit_identical(res, queries.bc_dependencies(g2, 0))
    assert validate_incremental(g2, 0, res, "bc")


def test_incremental_bc_source_level_dirt_falls_back_to_full():
    """A cut of 0 (the source itself suspect) cannot warm-start: full."""
    g, n = _chain_graph(depth=4, width=2)
    prior, _ = incremental_bc(g, None, None, 0)
    g2, _ = apply_ops(g, [(PUTE, 0, 5, 1.0)])  # source out-list churn
    res, st = incremental_bc(g2, prior, dirty_vertices(g, g2), 0)
    # source dirty at level 0 -> cut 1 is still a valid warm start (only
    # level 0 is reused); dirt at the source's own liveness would cut 0
    assert st.mode in ("delta", "full")
    _assert_bit_identical(res, queries.bc_dependencies(g2, 0))
    g3, _ = apply_ops(g, [(REMV, 0)])
    res3, st3 = incremental_bc(g3, prior, dirty_vertices(g, g3), 0)
    assert st3.mode == "full"  # dead source: cut 0
    _assert_bit_identical(res3, queries.bc_dependencies(g3, 0))


def test_service_bc_scores_revived_source_not_unchanged():
    """Resurrecting a dead vertex gives it a non-empty forward tree, but
    its cached row is empty and intersects no dirty set — bc_scores must
    still recompute it (cold row inside the warm sweep)."""
    g = make_graph(16, 64)
    g, _ = apply_ops(g, [(PUTV, i) for i in range(8)]
                     + [(PUTE, 0, 1, 1.0), (PUTE, 1, 2, 1.0)])
    g, _ = apply_ops(g, [(REMV, 5)])
    svc = GraphService(g, batch_size=4)
    svc.bc_scores()
    svc.submit_many([(PUTV, 5), (PUTE, 5, 1, 1.0)])
    svc.flush()
    scores, _ = svc.bc_scores()
    assert svc.bc_scores_stats["unchanged"] == 0
    ref, _ = GraphService(svc.ring.latest.state).bc_scores()
    a, b = np.asarray(scores), np.asarray(ref)
    assert np.array_equal(np.isnan(a), np.isnan(b))
    assert np.array_equal(np.nan_to_num(a), np.nan_to_num(b))


def test_service_bc_scores_delta_bit_identical():
    """GraphService.bc_scores warm-starts all-source BC through the
    per-source level cut and stays bit-identical to a cold recompute."""
    rng = np.random.default_rng(21)
    svc = _service(rng)
    svc.bc_scores()
    svc.submit_many([(PUTE, 3, 9, 2.0), (REME, 5, 11), (PUTE, 40, 7, 1.0)])
    svc.flush()
    scores, ver = svc.bc_scores()
    assert svc.bc_scores_stats["delta"] == 1
    cold = GraphService(svc.ring.latest.state)
    ref, _ = cold.bc_scores()
    a, b = np.asarray(scores), np.asarray(ref)
    assert np.array_equal(np.isnan(a), np.isnan(b))
    assert np.array_equal(np.nan_to_num(a), np.nan_to_num(b))


def test_incremental_sssp_negative_cycle_matches_full():
    g = make_graph(8, 16)
    g, _ = apply_ops(g, [(PUTV, 0), (PUTV, 1), (PUTV, 2),
                         (PUTE, 0, 1, 1.0), (PUTE, 1, 2, 1.0)])
    prior, _ = incremental_sssp(g, None, None, 0)
    ops = [(PUTE, 2, 1, -5.0)]  # closes a negative cycle 1->2->1
    g2, _ = apply_ops(g, ops)
    res, stats = incremental_sssp(g2, prior, dirty_vertices(g, g2), 0)
    assert stats.mode == "full"  # negcycle forces the canonical full answer
    _assert_bit_identical(res, queries.sssp(g2, 0))
    assert bool(res.negcycle)


# ------------------------------ version ring ------------------------------

def test_ring_rotation_and_eviction():
    rng = np.random.default_rng(3)
    state = _seed_graph(rng)
    ring = VersionRing(state, depth=3)
    for _ in range(4):
        state, _ = apply_ops(state, _random_commit(rng))
        ring.commit(state)
    assert ring.latest.version == 4
    assert ring.oldest_version == 2
    assert ring.get(1) is None  # rotated out
    assert ring.get(3) is not None
    assert ring.evictions == 2  # versions 0 and 1


def test_ring_pin_survives_rotation():
    rng = np.random.default_rng(4)
    state = _seed_graph(rng)
    ring = VersionRing(state, depth=2)
    pin = ring.pin()  # pins version 0
    pinned_edges = _edge_set(pin.state)
    for _ in range(3):
        state, _ = apply_ops(state, _random_commit(rng))
        ring.commit(state)
    assert ring.get(0) is not None  # parked, not evicted
    assert _edge_set(pin.state) == pinned_edges  # snapshot is immutable
    pin.release()
    assert ring.get(0) is None
    with pytest.raises(KeyError):
        ring.pin(0)


def test_ring_dirty_between():
    rng = np.random.default_rng(5)
    state = _seed_graph(rng)
    ring = VersionRing(state, depth=8)
    states = [state]
    for _ in range(3):
        state, _ = apply_ops(state, _random_commit(rng))
        ring.commit(state)
        states.append(state)
    span = np.asarray(ring.dirty_between(0, 3))
    direct = np.asarray(dirty_vertices(states[0], states[3]))
    # the ORed span covers every actual change (it may be a superset:
    # a vertex touched then reverted is dirty per-commit but not end-to-end)
    assert not np.any(direct & ~span)
    assert not np.any(np.asarray(ring.dirty_between(3, 3)))
    assert ring.dirty_between(0, 99) is None  # future version unknown
    with pytest.raises(ValueError):
        ring.dirty_between(3, 0)


def test_ring_dirty_between_evicted_span_is_none():
    rng = np.random.default_rng(6)
    state = _seed_graph(rng)
    ring = VersionRing(state, depth=2)
    for _ in range(4):
        state, _ = apply_ops(state, _random_commit(rng))
        ring.commit(state)
    assert ring.dirty_between(0, ring.latest.version) is None
    assert ring.dirty_between(0, 0) is None  # empty span, evicted version
    assert ring.dirty_between(ring.latest.version - 1,
                              ring.latest.version) is not None


# ------------------------------- scheduler --------------------------------

def test_scheduler_auto_commits_full_batches():
    rng = np.random.default_rng(8)
    ring = VersionRing(_seed_graph(rng), depth=8)
    sched = StreamScheduler(ring, batch_size=4)
    for op in [(PUTE, 0, i, 1.0) for i in range(3)]:
        sched.submit(op)
    assert ring.latest.version == 0 and sched.pending() == 3
    sched.submit((PUTE, 0, 3, 1.0))  # fills the batch
    assert ring.latest.version == 1 and sched.pending() == 0
    assert sched.stats.batches_committed == 1
    sched.submit((REME, 0, 1))
    entries = sched.flush()  # drains the partial tail
    assert len(entries) == 1 and ring.latest.version == 2
    assert sched.stats.ops_committed == 5


def test_scheduler_rejects_reads():
    rng = np.random.default_rng(8)
    sched = StreamScheduler(VersionRing(_seed_graph(rng)), batch_size=4)
    with pytest.raises(ValueError):
        sched.submit(("GETV", 0))


def _committed_state(ops, **kw):
    ring = VersionRing(make_graph(16, 64), depth=64)
    sched = StreamScheduler(ring, **kw)
    sched.submit_many(ops)
    sched.flush()
    return ring.latest.state, sched


def test_scheduler_strict_order_equals_sequential():
    """strict_order history == applying every op one at a time, in order."""
    rng = np.random.default_rng(9)
    ops = [(PUTV, i) for i in range(8)]
    for _ in range(40):
        r = rng.random()
        u, v = int(rng.integers(0, 8)), int(rng.integers(0, 8))
        if r < 0.15:
            ops.append((REMV, u))
        elif r < 0.3:
            ops.append((PUTV, u))
        elif r < 0.7:
            ops.append((PUTE, u, v, float(rng.integers(1, 5))))
        else:
            ops.append((REME, u, v))
    strict, sched = _committed_state(ops, batch_size=8, strict_order=True)
    assert sched.stats.strict_cuts > 0  # the guarantee was actually needed
    seq = make_graph(16, 64)
    for op in ops:
        seq, _ = apply_ops(seq, [op])
    assert _edge_set(strict) == _edge_set(seq)
    assert np.array_equal(np.asarray(strict.alive), np.asarray(seq.alive))


def test_scheduler_coalesce_preserves_state():
    ops = [(PUTV, 0), (PUTV, 1), (PUTV, 2)]
    ops += [(PUTE, 0, 1, float(w)) for w in (1, 2, 3)]  # same key x3
    ops += [(PUTE, 1, 2, 9.0), (REME, 1, 2)]            # put then rem
    plain, _ = _committed_state(list(ops), batch_size=32)
    coal, sched = _committed_state(list(ops), batch_size=32, coalesce=True)
    assert sched.stats.ops_coalesced == 3
    assert _edge_set(plain) == _edge_set(coal) == {(0, 1, 3.0)}


# ------------------------------ GraphService ------------------------------

def _service(rng, **kw):
    return GraphService(_seed_graph(rng), batch_size=8, ring_depth=8, **kw)


def test_service_icn_incremental_path_matches_fresh():
    rng = np.random.default_rng(10)
    svc = _service(rng)
    r0 = svc.query("bfs", 0)
    assert r0.mode == "full" and r0.version == 0
    r1 = svc.query("bfs", 0)  # nothing committed since: cached answer
    assert r1.mode == "unchanged"
    for _ in range(3):
        svc.submit_many(_random_commit(rng, vertex_churn=False))
        svc.flush()
        r = svc.query("bfs", 0)
        assert r.version == svc.version
        _assert_bit_identical(r.result, queries.bfs(svc.ring.latest.state, 0))
    assert svc.stats.delta > 0


def test_service_cn_double_collect_validates():
    rng = np.random.default_rng(11)
    svc = _service(rng)
    svc.submit_many(_random_commit(rng))
    svc.flush()
    r = svc.query("sssp", 0, mode="cn")
    assert r.validated and r.scan.collects >= 2
    _assert_bit_identical(r.result,
                          queries.sssp(svc.ring.latest.state, 0))


def test_service_cn_consumes_pending_updates_between_collects():
    rng = np.random.default_rng(12)
    svc = _service(rng)
    svc.query("bfs", 0)
    # leave updates pending (no flush): cn's interrupting commit_one drains
    # one batch between collects, so the answer lands on a newer version
    svc.submit_many([(PUTE, 0, i, 1.0) for i in range(1, 6)])
    assert svc.scheduler.pending() > 0
    r = svc.query("bfs", 0, mode="cn")
    assert r.validated
    assert r.version > 0
    _assert_bit_identical(r.result, queries.bfs(svc.ring.latest.state, 0))


def test_service_cache_eviction_is_lru():
    rng = np.random.default_rng(14)
    svc = _service(rng, max_cached=2)
    svc.query("bfs", 0)
    svc.query("bfs", 1)
    svc.query("bfs", 0)  # refresh 0: it is now the most recent
    svc.query("bfs", 2)  # evicts 1, not 0
    assert ("bfs", 0) in svc._cache and ("bfs", 1) not in svc._cache
    r = svc.query("bfs", 0)
    assert r.mode == "unchanged"  # the hot key survived eviction


def test_service_rejects_unknown_kind_and_mode():
    rng = np.random.default_rng(13)
    svc = _service(rng)
    with pytest.raises(KeyError):
        svc.query("pagerank", 0)
    for kind in ("bfs", "bc"):
        with pytest.raises(ValueError):
            svc.query(kind, 0, mode="maybe")


def test_service_bc_supports_cn_double_collect():
    rng = np.random.default_rng(15)
    svc = _service(rng)
    r = svc.query("bc", 0, mode="cn")
    # The first collect recomputes; the second lands on the same version and
    # the (kind, src) cache answers it as "unchanged" — BC now shares the
    # BFS/SSSP snapshot/cache semantics.
    assert r.validated and r.scan.collects >= 2
    _assert_bit_identical(r.result,
                          queries.bc_dependencies(svc.ring.latest.state, 0))


def test_service_bc_cache_semantics_match_bfs():
    """BC is a cached query kind with the full unchanged/delta/full ladder:
    every mode is bit-identical to a fresh ``bc_dependencies``."""
    rng = np.random.default_rng(16)
    svc = _service(rng)
    r0 = svc.query("bc", 0)
    assert r0.mode == "full"
    r1 = svc.query("bc", 0)  # nothing committed since
    assert r1.mode == "unchanged" and r1.result is r0.result
    modes = set()
    for _ in range(6):
        svc.submit_many(_random_commit(rng, vertex_churn=False))
        svc.flush()
        r = svc.query("bc", 0)
        modes.add(r.mode)
        assert r.version == svc.version
        _assert_bit_identical(
            r.result, queries.bc_dependencies(svc.ring.latest.state, 0))
    assert "delta" in modes  # the level-cut path actually exercised


def test_service_bc_unchanged_outside_reached_region():
    g = make_graph(64, 256)
    g, _ = apply_ops(g, [(PUTV, i) for i in range(64)] + [(PUTE, 0, 1, 1.0)])
    svc = GraphService(g, batch_size=4, ring_depth=8)
    r0 = svc.query("bc", 0)  # reaches only {0, 1}
    svc.submit_many([(PUTE, 10, i, 1.0) for i in range(20, 24)])
    svc.flush()
    r1 = svc.query("bc", 0)
    assert r1.mode == "unchanged" and r1.result is r0.result


def test_service_bc_scores_incremental_tile_view():
    """bc_scores runs the batched Brandes over an incrementally refreshed
    tile view and matches the per-source map baseline."""
    from repro.core import build_tile_view
    rng = np.random.default_rng(17)
    svc = _service(rng)
    scores0, v0 = svc.bc_scores()
    svc.submit_many(_random_commit(rng))
    svc.flush()
    scores1, v1 = svc.bc_scores()
    assert v1 > v0
    state = svc.ring.latest.state
    # the incrementally refreshed view is identical to a fresh build
    fresh = build_tile_view(state)
    assert np.array_equal(np.asarray(svc._tiles.w), np.asarray(fresh.w))
    assert np.array_equal(np.asarray(svc._tiles.occ), np.asarray(fresh.occ))
    for v in (0, 7, 33):
        ref = float(queries.bc(state, v, method="map"))
        got = float(np.asarray(scores1)[v])
        if np.isnan(ref):
            assert np.isnan(got)
        else:
            assert got == pytest.approx(ref, rel=1e-4, abs=1e-4)


# ------------------------- ring edge semantics ----------------------------

def test_ring_release_is_idempotent_and_tolerates_unpinned():
    """Double release of a pin and release of a never-pinned version are
    both no-ops: counts never go negative, residency never changes."""
    rng = np.random.default_rng(20)
    state = _seed_graph(rng)
    ring = VersionRing(state, depth=3)
    ring.release(0)     # never pinned: no-op
    ring.release(99)    # never existed: no-op
    assert ring.pinned_versions() == [] and ring.get(0) is not None

    pin = ring.pin(0)
    pin.release()
    pin.release()       # handle-level idempotence
    ring.release(0)     # and a third, direct, release: still a no-op
    assert ring.pinned_versions() == []
    assert ring.get(0) is not None  # still resident: release != evict

    # two pins on one version need two releases
    ring.pin(0)
    ring.pin(0)
    ring.release(0)
    assert ring.pinned_versions() == [0]
    ring.release(0)
    assert ring.pinned_versions() == []


def test_ring_parked_entry_keeps_serving_after_rotation():
    """A pinned version rotated out of the window parks: get/get_entry and
    snapshot reads keep working until the last release, which evicts it."""
    rng = np.random.default_rng(21)
    state = _seed_graph(rng)
    ring = VersionRing(state, depth=2)
    pin = ring.pin(0)
    for _ in range(4):
        state, _ = apply_ops(state, _random_commit(rng))
        ring.commit(state)
    assert ring.oldest_version == 3        # 0 long gone from the window
    entry = ring.get_entry(0)
    assert entry is not None and entry.version == 0
    assert _edge_set(pin.state) == _edge_set(entry.state)
    # dirty history is window-only: parked entries never resurrect spans
    assert ring.dirty_between(0, ring.latest.version) is None
    evictions = ring.evictions
    pin.release()
    assert ring.get_entry(0) is None and ring.evictions == evictions + 1


def test_ring_dirty_between_across_vcap_growth():
    """A span that crosses a vertex-table growth pads the narrower masks:
    the result is sized to the newest state's vcap with no phantom dirt in
    the grown region."""
    from repro.core import grow_vertices
    rng = np.random.default_rng(22)
    state = _seed_graph(rng)
    vcap0 = state.vcap
    ring = VersionRing(state, depth=8)
    state, _ = apply_ops(state, _random_commit(rng))
    ring.commit(state)                         # v1 @ vcap0
    state = grow_vertices(state)
    state, _ = apply_ops(state, _random_commit(rng))
    ring.commit(state)                         # v2 @ 2*vcap0
    assert state.vcap > vcap0
    span = ring.dirty_between(0, 2)
    assert span is not None and span.shape[0] == state.vcap
    # commits only touched ids < vcap0: the grown region must be clean
    assert not bool(np.asarray(span)[vcap0:].any())
    # the padded span still covers the end-to-end dirty set
    per = [np.asarray(ring.get_entry(v).dirty) for v in (1, 2)]
    ored = np.zeros((state.vcap,), bool)
    for m in per:
        ored[: m.shape[0]] |= m
    assert np.array_equal(np.asarray(span), ored)
    # an empty span anchored at the narrow version sizes to THAT vcap
    assert np.asarray(ring.dirty_between(1, 1)).shape[0] == vcap0


# ----------------------- heartbeat / straggler wiring ----------------------

def test_scheduler_heartbeat_flags_slow_commits():
    """A HeartbeatMonitor handed to the scheduler watches commit latency:
    with factor=0 every commit after the 8-sample warmup is a straggler —
    counted on the monitor, mirrored into scheduler_stragglers, and
    annotated on the commit's trace span."""
    from repro.obs import Telemetry
    from repro.runtime.fault_tolerance import HeartbeatMonitor

    rng = np.random.default_rng(23)
    flagged = []
    mon = HeartbeatMonitor(window=32, factor=0.0,
                           on_straggler=lambda v, dt, med: flagged.append(v))
    tel = Telemetry.make(None)
    svc = GraphService(_seed_graph(rng), batch_size=4, telemetry=tel,
                       monitor=mon)
    for _ in range(6):
        svc.submit_many(_random_commit(rng, n_ops=8))
        svc.flush()
    n = svc.scheduler.stats.batches_committed
    assert n >= 10
    assert mon.stragglers == n - 8 == svc.scheduler.stats.stragglers
    assert flagged and flagged[0] == 9  # ring version of the 9th commit
    commits = [r for r in tel.tracer.records if r["span"] == "commit"]
    assert sum(bool(r.get("straggler")) for r in commits) == mon.stragglers
    assert len(mon.window) == n
    tel.close()


def test_scheduler_counts_invalidated_edges():
    """``edges_invalidated`` adds the live edges each committed RemV
    killed, counted exactly (a tombstone is not killed twice), stays put
    across edge-only commits, and is the ``killed`` attribute of the
    commit's ``apply`` span."""
    from repro.obs import Telemetry

    g = make_graph(VCAP, ECAP)
    g, _ = apply_ops(g, [(PUTV, i) for i in range(8)] + [
        (PUTE, 0, 1, 1.0), (PUTE, 1, 0, 1.0), (PUTE, 2, 1, 1.0),
        (PUTE, 1, 3, 1.0), (PUTE, 4, 5, 1.0), (PUTE, 1, 6, 1.0)])
    g, _ = apply_ops(g, [(REME, 1, 6)])          # a tombstone at vertex 1
    tel = Telemetry.make(None, hlo=False)
    sched = StreamScheduler(VersionRing(g, depth=8), batch_size=4,
                            telemetry=tel)
    sched.submit_many([(PUTE, 4, 6, 2.0), (REME, 4, 5), (PUTE, 6, 7, 1.0),
                       (PUTE, 0, 2, 1.0)])
    assert sched.stats.edges_invalidated == 0
    sched.submit_many([(REMV, 1), (REMV, 7), (PUTE, 3, 4, 1.0), (REMV, 1)])
    assert sched.stats.edges_invalidated == 4 + 1   # 1's four, 7's one
    sched.submit_many([(PUTV, 1), (PUTE, 1, 2, 1.0), (PUTE, 2, 3, 1.0),
                       (REME, 0, 2)])
    assert sched.stats.edges_invalidated == 5
    assert sched.stats.batches_committed == 3
    applies = [r for r in tel.tracer.records if r["span"] == "apply"]
    assert [r["killed"] for r in applies] == [0, 5, 0]
    tel.close()
