"""One version per sharded collect, and stale groups re-pinned on the
batched path, on a 4-way mesh of host devices.

A collect reads and pins the ring's latest version, then refreshes the tile
view and launches its rungs.  A commit landing between the two must not
reach the answer: the race cases commit from a hook on the view refresh
(no sleeps, no threads) and compare each reply, full and delta rung of
every kind, with the sequential oracle at the version the reply names.

Three querying threads beside a committer, with a short switch interval,
check the same of collects that run concurrently.

The re-pin cases hold the dispatcher on a gate while requests are admitted
at one version and a commit lands, so every group reaches
``_dispatch_dedup`` behind the latest version.  Each is answered at the
latest version with one collect per distinct source, and none falls back
to the per-request path; once with telemetry (records, registry counter),
once without it under a profiler session, as the benchmark serves.

All of it runs in one child process (``SCRIPT``), which prints one
``RESULT`` line per case.
"""
import json

import pytest

from conftest import run_multidevice

KINDS = ("bfs", "sssp", "bc")
RACE = [f"{kind}/{rung}" for kind in KINDS for rung in ("full", "delta")]

SCRIPT = r'''
import copy, glob, json, os, shutil, sys, tempfile, threading
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
from jax.profiler import ProfileData
from repro.core import PUTE, PUTV, apply_ops, make_graph
from repro.obs import Telemetry
from repro.serve import AsyncGraphService
from repro.shard import ShardedGraphService, as_graph_mesh
from oracle import GraphOracle
from stream_differential import _CHECK, _apply_oracle

VCAP, SEG = 128, 16          # one directed path of 16 vertices per case
MESH = as_graph_mesh()
assert MESH.devices.size == 4


def emit(case, **out):
    print("RESULT " + json.dumps({"case": case, **out}), flush=True)


def base_ops():
    ops = [(PUTV, v) for v in range(VCAP)]
    return ops + [(PUTE, b + i, b + i + 1, 1.0) for b in range(0, VCAP, SEG)
                  for i in range(SEG - 1)]


class Stream:
    """A service and the oracle of every version it committed."""

    def __init__(self, telemetry=None):
        ops = base_ops()
        g, _ = apply_ops(make_graph(VCAP, 1024), ops)
        self.oracle = GraphOracle()
        _apply_oracle(self.oracle, ops)
        self.svc = ShardedGraphService(
            g, MESH, tile=16, batch_size=64, bc_mode="ring",
            dirty_threshold=1.0, telemetry=telemetry)
        self.oracles = {0: copy.deepcopy(self.oracle)}

    def commit(self, ops):
        _apply_oracle(self.oracle, ops)
        self.svc.submit_many(ops)
        self.svc.flush()
        self.oracles[self.svc.version] = copy.deepcopy(self.oracle)

    def check(self, ctx, kind, src, reply):
        _CHECK[kind](ctx, reply, self.oracles[reply.version], src, VCAP,
                     True)


def race():
    tel = Telemetry(block=False, accountant=None)
    st = Stream(tel)
    armed = []
    real_view = ShardedGraphService.view

    def hooked(self, *a, **k):
        """A commit lands after the collect read the ring, before the
        view is refreshed."""
        if armed:
            st.commit(armed.pop())
        return real_view(self, *a, **k)

    ShardedGraphService.view = hooked
    for i, case in enumerate(%(race)r):
        kind, rung = case.split("/")
        b = i * SEG
        try:
            if rung == "delta":
                assert st.svc.query(kind, [b]).mode == "full"
                st.commit([(PUTE, b + 2, b + 5, 1.0)])    # touches the tree
                hook = (PUTE, b + 6, b + 12, 1.0)          # below the cut
            else:
                hook = (PUTE, b, b + 8, 1.0)
            armed.append([hook])
            v0 = st.svc.version
            reply = st.svc.query(kind, [b])
            assert not armed and st.svc.version == v0 + 1, "hook not fired"
            assert reply.mode == rung, reply.mode
            st.check((case, reply.version), kind, b, reply)
            emit(case, ok=True, version=reply.version)
        except AssertionError as e:
            emit(case, ok=False, error=repr(e)[:500])
    ShardedGraphService.view = real_view
    spans = [r for r in tel.tracer.records if r["span"] == "collect"]
    emit("collect_spans", n=len(spans),
         mismatched=[r for r in spans if r.get("pinned") != r["version"]])


def repin(telemetry):
    """Seven requests admitted at version 0, answered after version 1."""
    st = Stream(telemetry)
    srv = AsyncGraphService(st.svc, max_batch=32)
    held, gate = threading.Event(), threading.Event()
    real_dispatch = srv._dispatch

    def gated(batch):
        if not held.is_set():
            held.set()
            gate.wait(120)
        real_dispatch(batch)

    srv._dispatch = gated
    collects = []
    real_collect = st.svc._traced_collect

    def counted(kind, srcs, key, **k):
        collects.append((kind, key))
        return real_collect(kind, srcs, key, **k)

    st.svc._traced_collect = counted
    asked = [("bfs", 0)] + [("bfs", 16), ("bfs", 16), ("bfs", 32),
                            ("sssp", 16), ("bc", 48), ("bc", 48)]
    with srv:
        futs = [srv.query_async(*asked[0])]
        assert held.wait(120)      # the dispatcher holds the first request
        futs += [srv.query_async(kind, src) for kind, src in asked[1:]]
        st.commit([(PUTE, 0, 8, 1.0), (PUTE, 16, 24, 1.0),
                   (PUTE, 32, 40, 1.0), (PUTE, 48, 56, 1.0)])
        gate.set()
        replies = [f.result(timeout=300) for f in futs]
    wrong = []
    for (kind, src), reply in zip(asked, replies):
        try:
            st.check((kind, src), kind, src, reply)
        except AssertionError as e:
            wrong.append(repr(e)[:300])
    out = {"versions": [r.version for r in replies],
           "modes": [r.mode for r in replies],
           "wrong": wrong, "fallbacks": srv.stats.fallbacks,
           "repinned": srv.stats.repinned, "picked": srv.stats.picked,
           "collects": sorted(map(str, collects)),
           "pins_left": st.svc.ring.pinned_versions()}
    if telemetry is not None:
        recs = telemetry.tracer.records
        out["registry"] = sum(c.value for c in telemetry.registry.find(
            "serve_repinned", service="sharded"))
        out["spans"] = {name: [{k: r.get(k) for k in (
            "kind", "lanes", "pinned", "version")} for r in recs
            if r["span"] == name] for name in ("repin", "collect")}
    return out


def profiled(body):
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d)
    try:
        out = body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(d, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    events = sorted((e.start_ns, e.name[len("repro."):], dict(e.stats))
                    for plane in ProfileData.from_file(path).planes
                    if plane.name.startswith("/host:")
                    for line in plane.lines for e in line.events
                    if e.name in ("repro.repin", "repro.collect"))
    shutil.rmtree(d)
    out["spans"] = {name: [{k: s.get(k) for k in (
        "kind", "lanes", "pinned", "version")} for _, n, s in events
        if n == name] for name in ("repin", "collect")}
    return out


def concurrent():
    """Three querying threads and a committer, with a short switch
    interval: the collect lock keeps each collect on one version."""
    st = Stream()
    got, errors = [], []

    def ask(t):
        for j in range(12):
            kind, src = %(kinds)r[j %% 3], ((t * 5 + j) %% 8) * SEG
            try:
                got.append((kind, src, st.svc.query(kind, [src])))
            except Exception as e:
                errors.append(repr(e)[:300])

    def commit():
        for i in range(10):
            b = (i %% 8) * SEG + i %% 5
            st.commit([(PUTE, b + 1, b + 6, 1.0)])

    threads = [threading.Thread(target=ask, args=(t,)) for t in range(3)]
    threads.append(threading.Thread(target=commit))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
    finally:
        sys.setswitchinterval(old)
    wrong = []
    for kind, src, reply in got:
        try:
            st.check((kind, src, reply.version), kind, src, reply)
        except AssertionError as e:
            wrong.append(repr(e)[:300])
    emit("concurrent", replies=len(got), errors=errors, wrong=wrong,
         alive=sum(t.is_alive() for t in threads),
         versions=sorted({r.version for _, _, r in got}))


race()
concurrent()
emit("repin_traced", **repin(Telemetry(block=False, accountant=None)))
emit("repin_profiled", **profiled(lambda: repin(None)))
''' % {"race": RACE, "kinds": KINDS}


@pytest.fixture(scope="module")
def results():
    out = run_multidevice(SCRIPT)
    res = {}
    for line in out.splitlines():
        if line.startswith("RESULT "):
            r = json.loads(line[len("RESULT "):])
            res[r.pop("case")] = r
    return res


@pytest.mark.parametrize("case", RACE)
def test_commit_between_read_and_refresh_stays_out_of_the_answer(results,
                                                                  case):
    """The reply equals the oracle at the version it names, though a
    commit landed between the collect's read of the ring and the view
    refresh."""
    assert results[case]["ok"], results[case]


def test_collect_spans_name_the_version_they_ran_on(results):
    spans = results["collect_spans"]
    assert spans["n"] >= len(RACE) * 3 // 2
    assert spans["mismatched"] == []


def test_concurrent_collects_and_commits_stay_exact(results):
    """Collecting threads beside the committer: no reply mixes versions
    and no collect meets a view another one refreshed away."""
    r = results["concurrent"]
    assert r["alive"] == 0
    assert r["errors"] == [] and r["wrong"] == []
    assert r["replies"] == 36 and len(r["versions"]) > 1


@pytest.mark.parametrize("how", ["traced", "profiled"])
def test_stale_group_is_repinned_not_fallen_back(results, how):
    """Seven requests admitted at version 0, dispatched after version 1
    committed: all re-pinned and answered exactly at version 1 by one
    collect per distinct ``(kind, source)`` of each dispatch."""
    r = results[f"repin_{how}"]
    assert r["wrong"] == [] and r["fallbacks"] == 0
    assert r["versions"] == [1] * 7
    assert r["repinned"] == r["picked"] == 7
    assert r["pins_left"] == []
    # the held request alone, then the six others in one dispatch: four
    # distinct (kind, source) pairs among them
    assert len(r["collects"]) == 1 + 4
    repins = r["spans"]["repin"]
    assert sorted((s["kind"], s["lanes"]) for s in repins) == [
        ("bc", 2), ("bfs", 1), ("bfs", 3), ("sssp", 1)]
    assert all((s["pinned"], s["version"]) == (0, 1) for s in repins)
    collects = r["spans"]["collect"]
    assert len(collects) == 5
    assert all(s["pinned"] == s["version"] == 1 for s in collects)
    if how == "traced":
        assert r["registry"] == 7
