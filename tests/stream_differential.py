"""Randomized differential op-stream harness.

One seeded RNG drives a mixed stream of vertex/edge mutations and
bfs/sssp/bc queries; the stream is replayed simultaneously against

  * the pure-python sequential oracle (``tests/oracle.py`` — the paper's
    ADT semantics, trusted by the PR-1 property tests),
  * the local :class:`repro.engine.GraphService`, and
  * (when given a mesh) the distributed
    :class:`repro.shard.ShardedGraphService` in either ``bc_mode``,

asserting after EVERY query that the service's answer — whatever rung of
the unchanged → delta → full ladder produced it — equals the oracle's at
that version.  The churn alternates between the half of the vertex range
the pinned sources live in and the far half, so one stream naturally
exercises all three ladder modes (the per-service mode tallies are
returned for the caller to assert on), plus the delta fallbacks: negative
weights (``neg_frac``) breed negative cycles mid-stream (delta SSSP must
fall back to the canonical full answer) and REMV/PUTV pairs resurrect
sources whose empty cached rows must restart cold.

Every replay also runs with telemetry attached (one shared
:class:`repro.obs.Telemetry` across the services): after the stream the
harness asserts ladder-mode *conservation* — ``unchanged + delta + full ==
stats.queries == #query trace records`` per service — and that the trace
records agree, in order, with every oracle-validated answer's
(kind, version, ladder mode).  ``trace_path`` additionally streams the
records to a JSONL file for ``python -m repro.obs.report``.

**Chaos mode** (``fault_plan=...``): the replay runs inside a
``repro.resil.fault_scope`` with a :class:`~repro.resil.ResiliencePolicy`
attached to every service, so injected faults hit the scheduler commits,
the collect ladder, ring eviction, and the result-cache stores mid-
stream.  The contract is **degraded-or-correct, never silently wrong**:

  * a commit that faults is retried until it lands — the scheduler's
    atomicity guarantee means the retry replays the identical prefix;
  * a successful (non-degraded) answer is checked against the oracle
    exactly as in a clean run;
  * a ``degraded=True`` answer must reproduce, bit-for-bit
    (``results_equal``), a previously oracle-validated answer at its
    still-resident ``stale_version``;
  * a query that raises (ladder exhausted, nothing cached) is counted —
    and ``verify_service`` must pass after EVERY injected fault.

Everything is keyed on the integer ``seed`` (logged on entry), so any
failure is reproducible with ``run_differential(seed, ...)`` alone; a
chaos failure additionally reproduces from
``FaultPlan(plan.to_schedule())``.
"""
from __future__ import annotations

import os

import numpy as np

from repro.core import PUTE, PUTV, REME, REMV, make_graph
from repro.engine import GraphService
from repro.engine.incremental import results_equal
from repro.obs import AdaptiveThresholds, Telemetry
from repro.resil import (
    InjectedFault,
    OpJournal,
    ResiliencePolicy,
    assert_service_ok,
    fault_scope,
    journal_meta,
    recover,
)
from oracle import GraphOracle

INF = float("inf")
WEIGHTS = (1.0, 2.0, 3.0)


# ------------------------------ stream gen ---------------------------------

def gen_ops(rng, lo: int, hi: int, count: int, neg_frac: float = 0.0):
    """One commit's worth of mixed ops confined to vertex range [lo, hi)."""
    ops = []
    for _ in range(count):
        r = float(rng.random())
        u = int(rng.integers(lo, hi))
        v = int(rng.integers(lo, hi))
        if r < 0.15:
            ops.append((PUTV, u))
        elif r < 0.25:
            ops.append((REMV, u))
        elif r < 0.85:
            w = (-1.0 if float(rng.random()) < neg_frac
                 else float(WEIGHTS[int(rng.integers(0, len(WEIGHTS)))]))
            ops.append((PUTE, u, v, w))
        else:
            ops.append((REME, u, v))
    return ops


def _apply_oracle(oracle: GraphOracle, ops) -> None:
    for op in ops:
        if op[0] == PUTV:
            oracle.put_v(op[1])
        elif op[0] == REMV:
            oracle.rem_v(op[1])
        elif op[0] == PUTE:
            oracle.put_e(op[1], op[2], op[3])
        else:
            oracle.rem_e(op[1], op[2])


# ------------------------------ checkers -----------------------------------

def _dense(m: dict, vcap: int, fill: float) -> np.ndarray:
    out = np.full((vcap,), fill, np.float64)
    for v, d in m.items():
        out[v] = d
    return out


def _first(x, sharded: bool):
    return x[0] if sharded else x


def check_bfs(ctx, reply, oracle, src, vcap, sharded):
    ref = oracle.bfs(src)
    ok = bool(_first(reply.result.ok, sharded))
    assert ok == (ref is not None), ctx
    dist = np.asarray(_first(reply.result.dist, sharded), np.float64)
    exp = _dense(ref or {}, vcap, -1.0)
    assert np.array_equal(dist, exp), ctx


def check_sssp(ctx, reply, oracle, src, vcap, sharded):
    ref, refneg = oracle.sssp(src)
    neg = bool(_first(reply.result.negcycle, sharded))
    ok = bool(_first(reply.result.ok, sharded))
    assert neg == refneg, ctx
    assert ok == (ref is not None and not refneg), ctx
    if ref is None or refneg:
        # a negative-cycle answer's partially-relaxed distances are only
        # canonical per implementation; the flag is the contract
        return
    dist = np.asarray(_first(reply.result.dist, sharded), np.float64)
    assert np.array_equal(dist, _dense(ref, vcap, INF)), ctx


def check_bc(ctx, reply, oracle, src, vcap, sharded):
    ref = oracle.bc_dependencies(src)
    ok = bool(_first(reply.result.ok, sharded))
    assert ok == (ref is not None), ctx
    if ref is None:
        return
    # levels ARE the oracle's BFS distances (hop metric), exactly
    level = np.asarray(_first(reply.result.level, sharded), np.float64)
    assert np.array_equal(level, _dense(oracle.bfs(src), vcap, -1.0)), ctx
    delta = np.asarray(_first(reply.result.delta, sharded), np.float64)
    assert np.allclose(delta, _dense(ref, vcap, 0.0),
                       rtol=1e-5, atol=1e-5), ctx


def check_scores(ctx, scores, oracle, vcap):
    ref = oracle.bc_scores()
    sc = np.asarray(scores, np.float64)
    for v in range(vcap):
        if v in ref:
            assert abs(sc[v] - ref[v]) <= 1e-4 * (1.0 + abs(ref[v])), (ctx, v)
        else:
            assert np.isnan(sc[v]), (ctx, v)


_CHECK = {"bfs": check_bfs, "sssp": check_sssp, "bc": check_bc}


# -------------------------------- runner -----------------------------------

def run_differential(seed: int, *, n: int = 24, steps: int = 8,
                     ops_per_step: int = 8, neg_frac: float = 0.0,
                     mesh=None, tile: int = 8, bc_mode: str = "gather",
                     batch_size: int = 4, score_every: int = 0,
                     trace_path=None, fault_plan=None, policy=None,
                     adaptive: bool = False, journal_dir=None,
                     compact_every=None, segment_bytes=None):
    """Replay one seeded stream against oracle + service(s).

    Returns ``{service_name: {"unchanged": k, "delta": k, "full": k,
    "degraded": k, "raised": k}}`` so callers can assert ladder-mode (and,
    in chaos runs, degradation) coverage.  Raises AssertionError (with
    the offending (service, kind, src, step, mode) context) on the first
    divergence from the oracle, and at the end on any telemetry
    inconsistency (mode-conservation or trace/answer disagreement — see
    module docstring).  ``trace_path`` mirrors the trace to a JSONL file.

    ``fault_plan`` (a ``repro.resil.FaultPlan``) turns on chaos mode: the
    whole replay runs inside its ``fault_scope`` and every service gets
    ``policy`` (default: 2 retries, stale serving on) — see the module
    docstring for the degraded-or-correct contract enforced per query.

    ``adaptive=True`` attaches an aggressive per-service
    :class:`~repro.obs.AdaptiveThresholds` controller (tight period,
    frequent probes) so the per-kind ``dirty_threshold`` actually moves
    mid-stream — every per-query oracle check then doubles as the proof
    that a moving threshold only re-routes queries between (bit-identical)
    ladder rungs.  The harness additionally asserts the controller
    invariants at the end (thresholds within clamps, one
    ``threshold_adjust`` span per adjustment) and returns each
    controller's snapshot under ``modes[name]["adaptive"]``.

    ``journal_dir`` attaches a durable :class:`~repro.resil.OpJournal`
    (``<dir>/<service>.jsonl``) to every service — with ``segment_bytes``
    rotation and ``compact_every`` auto-compaction if given — and after
    the stream runs the **recovery differential**: each journal is
    recovered into a fresh service (the sharded one under the same live
    mesh) whose ring latest must be bit-identical to the survivor's and
    whose cold query answers must match the oracle at the final version.
    The per-journal rotation/compaction tallies come back under
    ``modes[name]["recovery"]``.
    """
    print(f"[stream-differential] seed={seed} n={n} steps={steps} "
          f"ops_per_step={ops_per_step} neg_frac={neg_frac} "
          f"bc_mode={bc_mode} chaos={fault_plan is not None}", flush=True)
    rng = np.random.default_rng(seed)
    g0 = make_graph(n, 16 * n)
    oracle = GraphOracle()
    telemetry = Telemetry.make(trace_path, hlo=mesh is not None)
    if fault_plan is not None and policy is None:
        policy = ResiliencePolicy(max_retries=2)

    def make_adaptive():
        # Aggressive settings: small graphs + short streams must still see
        # adjustments and probes, or the adaptive assertions test nothing.
        return (AdaptiveThresholds(period=6, min_full=1, min_delta=3,
                                   probe_every=7) if adaptive else None)

    journals = {}

    def make_journal(name):
        if journal_dir is None:
            return None
        path = os.path.join(str(journal_dir), f"{name}.jsonl")
        journals[name] = path
        return OpJournal(path,
                         meta=journal_meta(g0, {"batch_size": batch_size}),
                         segment_bytes=segment_bytes)

    services = [("local", GraphService(g0, batch_size=batch_size,
                                       telemetry=telemetry, policy=policy,
                                       adaptive=make_adaptive(),
                                       journal=make_journal("local"),
                                       compact_every=compact_every),
                 False)]
    if mesh is not None:
        from repro.shard import ShardedGraphService
        services.append(("sharded", ShardedGraphService(
            g0, mesh, tile=tile, batch_size=batch_size, bc_mode=bc_mode,
            src_chunk=2, telemetry=telemetry, policy=policy,
            adaptive=make_adaptive(), journal=make_journal("sharded"),
            compact_every=compact_every), True))
    modes = {name: {"unchanged": 0, "delta": 0, "full": 0, "degraded": 0,
                    "raised": 0}
             for name, _, _ in services}
    # Every oracle-validated explicit query's (kind, version, mode), in
    # submission order, per service — checked against the trace at the end.
    expected = {name: [] for name, _, _ in services}
    # Every oracle-validated answer, keyed (kind, src, version), per
    # service — the reference a degraded reply must reproduce exactly.
    validated = {name: {} for name, _, _ in services}

    def commit(ops):
        _apply_oracle(oracle, ops)
        for name, svc, _ in services:
            for op in ops:
                # A submit can fault in its auto-commit; the op itself is
                # already in the log (append precedes commit), and the
                # failed chunk went back — a later commit drains both.
                try:
                    svc.submit(op)
                except InjectedFault:
                    assert_service_ok(svc)
            # Under faults a commit may fail mid-flush; atomicity puts the
            # chunk back, so retrying drains the identical prefix.  The
            # service must verify clean after EVERY injected failure.
            # (Progress is monotone: every retry that lands >= 1 batch
            # shrinks the log, so the bound only guards a pathological
            # plan that fails every single attempt.)
            for _ in range(256):
                try:
                    svc.flush()
                    break
                except InjectedFault:
                    assert_service_ok(svc)
            else:
                raise AssertionError(
                    (seed, name, "commit never succeeded under faults"))

    def run_query(name, svc, sharded, kind, src, step):
        ctx = (name, kind, src, step, seed)
        try:
            reply = svc.query(kind, [src] if sharded else src)
        except InjectedFault:
            # ladder exhausted with nothing servable cached: a LOUD
            # failure (never a wrong answer); service must still verify
            modes[name]["raised"] += 1
            assert_service_ok(svc)
            return
        if reply.degraded:
            modes[name]["degraded"] += 1
            assert reply.stale_version == reply.version, (ctx, reply)
            assert svc.ring.get_entry(reply.stale_version) is not None, ctx
            prev = validated[name].get((kind, src, reply.stale_version))
            assert prev is not None, (ctx, "degraded reply at a version "
                                      "that was never validated")
            assert results_equal(reply.result, prev), (
                ctx, "degraded reply differs from the validated answer "
                "at its claimed version")  # the no-torn-reads check
        else:
            modes[name][reply.mode] += 1
            _CHECK[kind]((*ctx, reply.mode), reply, oracle, src, n, sharded)
            validated[name][(kind, src, reply.version)] = reply.result
            expected[name].append((kind, reply.version, reply.mode))
        if fault_plan is not None:
            assert_service_ok(svc)

    with fault_scope(fault_plan):
        # Base population: every vertex alive, a random edge set per HALF
        # of the range — churn then alternates halves, so queries pinned
        # in the lower half see far commits (unchanged), near commits
        # (delta), and their own cold collects (full).
        half = n // 2
        base = [(PUTV, i) for i in range(n)]
        for lo, hi in ((0, half), (half, n)):
            for _ in range(3 * half):
                base.append((PUTE, int(rng.integers(lo, hi)),
                             int(rng.integers(lo, hi)),
                             float(WEIGHTS[int(
                                 rng.integers(0, len(WEIGHTS)))])))
        commit(base)

        pinned = [0, 1]
        for step in range(steps):
            lo, hi = ((half, n) if step % 2 else (0, half))
            commit(gen_ops(rng, lo, hi, ops_per_step, neg_frac))
            for src in pinned + [int(rng.integers(0, n))]:
                for kind in ("bfs", "sssp", "bc"):
                    for name, svc, sharded in services:
                        run_query(name, svc, sharded, kind, src, step)
            if score_every and (step + 1) % score_every == 0:
                for name, svc, _ in services:
                    scores, _ = svc.bc_scores()
                    check_scores((name, "bc_scores", step, seed), scores,
                                 oracle, n)
    for name, svc, _ in services:
        # fault attribution for chaos callers: retries/errors only move
        # when the ladder (i.e. a collect) actually failed on THIS service
        modes[name]["errors"] = svc.stats.errors
        modes[name]["retries"] = svc.stats.retries
    _check_telemetry(seed, telemetry, services, modes, expected)
    if adaptive:
        _check_adaptive(seed, telemetry, services, modes)
    if journal_dir is not None:
        _check_recovery(seed, services, journals, g0, oracle, n, modes,
                        mesh=mesh, tile=tile, bc_mode=bc_mode,
                        batch_size=batch_size)
    telemetry.close()
    return modes


def _check_recovery(seed, services, journals, g0, oracle, n, modes, *,
                    mesh, tile, bc_mode, batch_size):
    """Recovery differential: every journaled service must rebuild — from
    its (possibly rotated + compacted) WAL alone — into a fresh service
    whose ring latest is bit-identical to the survivor's, whose pending
    log depth matches, and whose cold query answers (full collects, no
    cache) equal the oracle's at the final version.  The sharded journal
    recovers under the same live mesh, proving replayed commits reproduce
    sharded query answers exactly."""
    import jax

    for name, svc, sharded in services:
        ctx = (seed, name, "recovery")
        path = journals[name]
        if sharded:
            from repro.shard import ShardedGraphService

            def make_service(state, **kw):
                return ShardedGraphService(state, mesh, tile=tile,
                                           bc_mode=bc_mode, src_chunk=2,
                                           **kw)
        else:
            make_service = None
        rec = recover(path, g0, make_service=make_service,
                      batch_size=batch_size)
        assert rec.version == svc.version, (ctx, rec.version, svc.version)
        for a, b in zip(jax.tree_util.tree_leaves(svc.ring.latest.state),
                        jax.tree_util.tree_leaves(rec.ring.latest.state)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), ctx
        assert rec.scheduler.pending() == svc.scheduler.pending(), ctx
        assert_service_ok(rec)
        for kind in ("bfs", "sssp", "bc"):
            for src in (0, 1):
                reply = rec.query(kind, [src] if sharded else src)
                assert reply.version == svc.version, (ctx, kind, src)
                _CHECK[kind]((*ctx, kind, src), reply, oracle, src, n,
                             sharded)
        j = svc.scheduler.journal
        modes[name]["recovery"] = {
            "version": int(rec.version),
            "rotations": j.rotations,
            "compactions": j.compactions,
            "segments_dropped": j.segments_dropped,
        }


def _check_telemetry(seed, telemetry, services, modes, expected):
    """Telemetry invariants over the whole replay (see module docstring).

    Records partition into *clean* (a successful collect — one per
    ``stats.queries``), *degraded* (stale serves — one per
    ``stats.degraded``), and *error* (the query raised; the record
    carries ``error`` and no version/mode) — each reconciled against its
    own counter, so the conservation invariants survive chaos runs.
    """
    assert telemetry.tracer.dropped == 0, seed
    for name, svc, _ in services:
        tally = modes[name]
        recs = [r for r in telemetry.tracer.records
                if r["span"] == "query" and r["service"] == name]
        err_recs = [r for r in recs if "error" in r]
        deg_recs = [r for r in recs if r.get("degraded")]
        clean = [r for r in recs
                 if "error" not in r and not r.get("degraded")]
        # Ladder-mode conservation: every successful query took exactly
        # one rung; degraded and error replies tally separately.
        assert (svc.stats.unchanged + svc.stats.delta + svc.stats.full
                == svc.stats.queries), (seed, name)
        assert len(clean) == svc.stats.queries, (seed, name)
        assert len(deg_recs) == svc.stats.degraded == tally["degraded"], \
            (seed, name)
        assert len(err_recs) == tally["raised"], (seed, name)
        # The explicit (oracle-validated) queries must appear in the trace
        # in order with matching kind/version/mode; bc_scores() on the
        # sharded service rides through query() and may interleave extra
        # "bc" records, hence subsequence rather than equality.
        it = iter(clean)
        for want in expected[name]:
            for rec in it:
                if (rec["kind"], rec["version"], rec["mode"]) == want:
                    break
            else:
                raise AssertionError((seed, name, "missing trace", want))
        per_mode = {m: sum(1 for r in clean if r["mode"] == m)
                    for m in ("unchanged", "delta", "full")}
        for m in per_mode:
            assert per_mode[m] >= tally[m], (seed, name, m)
        assert sum(per_mode.values()) == len(clean), (seed, name)


# ------------------------- concurrent replay -------------------------------

def run_concurrent_differential(seed: int, *, n: int = 24, chunks: int = 10,
                                ops_per_chunk: int = 4, clients: int = 3,
                                queries_per_client: int = 12,
                                neg_frac: float = 0.0, fault_plan=None,
                                policy=None, max_batch: int = 16,
                                trace_path=None):
    """Concurrent-schedule replay through the async serving front end.

    One seeded RNG fixes everything decidable up front — the base graph,
    the per-commit op chunks, and each client thread's query schedule —
    then ``clients`` query threads and one updater thread run against a
    single :class:`repro.serve.AsyncGraphService` concurrently.  The OS
    interleaving is NOT controlled (that is the point); correctness must
    not depend on it, because every reply pins the ring version it was
    admitted at:

      * each resolved reply is checked **at its own version** — the
        sequential oracle (``tests/oracle.py``) replays the committed
        chunk prefix to that version and the answer must match it
        semantically AND be bit-equal (``results_equal``) to a fresh
        sequential full collect on the reconstructed snapshot — the
        batched-dispatch bit-identity claim, enforced per reply;
      * chunk boundaries equal commit boundaries by construction (each
        chunk is exactly ``batch_size`` ops, auto-committed), so the
        state at version ``v`` is reproducible as ``apply_ops`` over the
        chunk prefix regardless of thread timing;
      * conservation must survive concurrency: ``unchanged + delta +
        full == stats.queries == #clean query trace records`` and
        degraded records == ``stats.degraded``.

    **Chaos mode** (``fault_plan=``): the whole run — admission, the
    dispatcher (which inherits the fault scope via its copied context),
    and the client commits — executes under the plan; the contract is
    the sequential harness's *degraded-or-correct, never silently
    wrong*: degraded replies are checked bit-exactly at their
    ``stale_version``; raising queries only count (``raised``) and must
    verify clean afterwards.

    Returns the mode tallies plus the front end's own counters
    (``serve`` key) so callers can assert batching actually happened.
    """
    print(f"[concurrent-differential] seed={seed} n={n} chunks={chunks} "
          f"ops_per_chunk={ops_per_chunk} clients={clients} "
          f"chaos={fault_plan is not None}", flush=True)
    import threading

    from repro.core import apply_ops
    from repro.core.queries import bc_dependencies, bfs, sssp
    from repro.serve import AsyncGraphService

    rng = np.random.default_rng(seed)
    half = n // 2
    base = [(PUTV, i) for i in range(n)]
    for lo, hi in ((0, half), (half, n)):
        for _ in range(3 * half):
            base.append((PUTE, int(rng.integers(lo, hi)),
                         int(rng.integers(lo, hi)),
                         float(WEIGHTS[int(rng.integers(0, len(WEIGHTS)))])))
    # Base population goes into version 0 directly (not through the
    # scheduler): v0 is then the well-known starting snapshot every
    # warm-up query pins, and version v == chunk prefix [0, v).
    g0, _ = apply_ops(make_graph(n, 16 * n), base)
    oracle = GraphOracle()
    _apply_oracle(oracle, base)

    chunk_list = [gen_ops(rng, *((half, n) if c % 2 else (0, half)),
                          ops_per_chunk, neg_frac)
                  for c in range(chunks)]
    pinned = [0, 1]
    schedules = []
    for _ in range(clients):
        sched = []
        for q in range(queries_per_client):
            kind = ("bfs", "sssp", "bc")[int(rng.integers(0, 3))]
            src = (pinned[int(rng.integers(0, len(pinned)))]
                   if float(rng.random()) < 0.7 else int(rng.integers(0, n)))
            sched.append((kind, src))
        schedules.append(sched)

    if fault_plan is not None and policy is None:
        policy = ResiliencePolicy(max_retries=2)
    telemetry = Telemetry.make(trace_path)
    svc = GraphService(g0, batch_size=ops_per_chunk, telemetry=telemetry,
                       policy=policy)

    results = [[] for _ in range(clients)]   # (kind, src, future)
    errs = []

    def updater(srv):
        try:
            for chunk in chunk_list:
                for op in chunk:
                    # a submit can fault inside its auto-commit; the op
                    # itself is already logged, and atomicity returned
                    # the chunk — a later commit drains it
                    try:
                        srv.submit(op)
                    except InjectedFault:
                        pass
            for _ in range(256):
                try:
                    srv.flush()
                    return
                except InjectedFault:
                    continue
            errs.append(AssertionError("flush never succeeded"))
        except Exception as e:  # pragma: no cover - harness guard
            errs.append(e)

    def querier(srv, idx):
        try:
            for kind, src in schedules[idx]:
                results[idx].append((kind, src,
                                     srv.query_async(kind, src)))
        except Exception as e:  # pragma: no cover - harness guard
            errs.append(e)

    with fault_scope(fault_plan):
        with AsyncGraphService(svc, max_batch=max_batch) as srv:
            # Warm-up burst at v0: populates the result cache (enabling
            # unchanged/delta rungs mid-stream) and is itself a batched
            # dispatch (many sources, one kind, one version).
            warm = [(k, s, srv.query_async(k, s))
                    for k in ("bfs", "sssp", "bc") for s in pinned]
            for _, _, f in warm:
                try:
                    f.result(timeout=120)
                except Exception:
                    assert fault_plan is not None, (seed, "warm raised")
            threads = [threading.Thread(target=updater, args=(srv,))]
            threads += [threading.Thread(target=querier, args=(srv, i))
                        for i in range(clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs, (seed, errs)
            assert srv.drain(timeout=300), (seed, "drain timed out")
    assert svc.version == chunks, (seed, svc.version, chunks)

    # ---- collect replies; only chaos runs may raise ----
    modes = {"unchanged": 0, "delta": 0, "full": 0, "degraded": 0,
             "raised": 0}
    by_version = {}
    for kind, src, fut in warm + [r for res in results for r in res]:
        try:
            reply = fut.result(timeout=120)
        except Exception as e:
            assert fault_plan is not None, (seed, kind, src, e)
            modes["raised"] += 1
            continue
        if reply.degraded:
            modes["degraded"] += 1
            assert reply.stale_version == reply.version, (seed, reply)
        else:
            modes[reply.mode] += 1
        assert 0 <= reply.version <= chunks, (seed, reply.version)
        by_version.setdefault(reply.version, []).append((kind, src, reply))

    # ---- sequential oracle replay: check every reply at its version ----
    fresh = {"bfs": bfs, "sssp": sssp, "bc": bc_dependencies}
    state = g0
    for v in range(0, chunks + 1):
        if v > 0:
            chunk = chunk_list[v - 1]
            _apply_oracle(oracle, chunk)
            state, _ = apply_ops(state, chunk, batch_size=ops_per_chunk)
        for kind, src, reply in by_version.get(v, ()):
            ctx = (seed, kind, src, v,
                   "degraded" if reply.degraded else reply.mode)
            _CHECK[kind](ctx, reply, oracle, src, n, False)
            # the bit-identity claim: every batched/pinned answer equals
            # a sequential full collect on the reconstructed snapshot
            assert results_equal(reply.result, fresh[kind](state, src)), \
                (ctx, "batched reply not bit-equal to sequential collect")
    assert_service_ok(svc)

    # ---- conservation under concurrency ----
    st = svc.stats
    assert st.unchanged + st.delta + st.full == st.queries, (seed, st)
    recs = [r for r in telemetry.tracer.records if r["span"] == "query"]
    clean = [r for r in recs if "error" not in r and not r.get("degraded")]
    deg = [r for r in recs if r.get("degraded")]
    assert len(clean) == st.queries, (seed, len(clean), st.queries)
    assert len(deg) == st.degraded == modes["degraded"], (seed, st.degraded)
    if fault_plan is None:
        assert modes["raised"] == 0 and st.errors == 0, (seed, modes)

    modes["errors"] = st.errors
    modes["retries"] = st.retries
    modes["serve"] = {
        "admitted": srv.stats.admitted,
        "dispatches": srv.stats.dispatches,
        "batched_dispatches": srv.stats.batched_dispatches,
        "fallbacks": srv.stats.fallbacks,
        "deadline_expired": srv.stats.deadline_expired,
        "max_batch_seen": srv.stats.max_batch_seen,
    }
    telemetry.close()
    return modes


def _check_adaptive(seed, telemetry, services, modes):
    """Controller invariants after an ``adaptive=True`` replay: every
    tuned threshold within its clamps, one ``threshold_adjust`` trace
    span per counted adjustment (carrying the decision inputs), and the
    gauge on the scrape surface agreeing with the controller."""
    for name, svc, _ in services:
        ctl = svc.adaptive
        assert ctl is not None, (seed, name)
        snap = ctl.snapshot()
        for kind, thr in snap["thresholds"].items():
            assert ctl.lo <= thr <= ctl.hi, (seed, name, kind, thr)
        adj_recs = [r for r in telemetry.tracer.records
                    if r["span"] == "threshold_adjust"
                    and r["service"] == name]
        assert len(adj_recs) == snap["adjustments"], (seed, name)
        for r in adj_recs:
            for f in ("old", "new", "t_full_us", "fit_slope_us",
                      "crossover", "n_full", "n_delta"):
                assert f in r, (seed, name, f)
            assert ctl.lo <= r["new"] <= ctl.hi, (seed, name, r)
        for kind in ctl.kinds:
            g = telemetry.registry.find("adaptive_dirty_threshold",
                                        service=name, kind=kind)
            assert len(g) == 1, (seed, name, kind)
            assert abs(g[0].value - snap["thresholds"][kind]) < 1e-9, \
                (seed, name, kind)
        modes[name]["adaptive"] = snap
