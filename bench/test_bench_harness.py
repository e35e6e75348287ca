"""The harness on the CPU at a tiny size: every cell runs to a correct
result through the program's served path; the same runs with the timed
path broken underneath come out not correct; the control is refused by
the limits; the command refuses to run without a TPU; and every name in
``BENCHMARK.json`` resolves to its files and keeps to the contract."""
import json
import os
import re
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import check, graphs, harness, peaks, reference, workload  # noqa: E402,E501

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SEED = 2**31 + 12345          # larger than 32 signed bits hold


@pytest.fixture
def no_compile_cache():
    """Keep the harness's persistent-cache settings out of this worker's
    later tests (and compiled CPU programs out of the checkout)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()


def tiny(cell: str):
    spec = harness.cell_spec(harness.load_benchmark(), cell)
    config = graphs.load_config(spec["config"])
    config["scale"] = 8
    traffic = workload.load_traffic(spec["traffic"])
    if "writer" in traffic:
        traffic["writer"]["max_ops_per_s"] = 200
    if "churn" in traffic:
        traffic["churn"]["rate_ops_per_s"] = 64
    return config, traffic


def run_tiny(cell: str, **kw):
    config, traffic = tiny(cell)
    return harness.run_cell(cell, SEED, 1.5, False,
                            t_process=time.perf_counter(), config=config,
                            traffic=traffic, **kw)


@pytest.mark.parametrize("cell", ["g500-served.hot", "g500-served.cold",
                                  "paper-rmat.ingest"])
def test_tiny_cell_is_correct(cell, no_compile_cache, capsys):
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    bench = harness.load_benchmark()
    want = {m["name"] for m in harness.metrics_for(bench, cell, False)}
    assert set(out["metrics"]) == want
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    logs = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith('{"phase"')]
    window = [x for x in logs if x["phase"] == "window"][0]
    assert window["compiles_in_window"] == 0
    if cell != "paper-rmat.ingest":
        checked = [x for x in logs if x["phase"] == "check"][0]
        rungs = {r.split("/")[1] for r in checked["rungs_checked"]}
        if cell.endswith("hot"):
            assert {"unchanged", "delta"} <= rungs
        else:
            assert rungs == {"full"}
        assert checked["shared_lanes_checked"] > 0


def _alter(rungs: str):
    def fault(monkeypatch):
        """BFS answers altered where a rung produces them."""
        from repro.serve import batch

        table = getattr(batch, rungs)
        rung = table["bfs"]

        def altered(state, *lanes):
            res = rung(state, *lanes)
            return res._replace(dist=res.dist + (res.dist > 0))

        monkeypatch.setitem(table, "bfs", altered)
    return fault


def _unchanged_state(monkeypatch):
    """A commit that returns the state it was given."""
    from repro.engine import scheduler

    apply_ops = scheduler.apply_ops

    def unchanged(state, ops, batch_size=None):
        _, res = apply_ops(state, ops, batch_size=batch_size)
        return state, res

    monkeypatch.setattr(scheduler, "apply_ops", unchanged)


def _half_batch(monkeypatch):
    """Half of each committed batch left out."""
    from repro.engine import scheduler

    apply_ops = scheduler.apply_ops

    def half(state, ops, batch_size=None):
        return apply_ops(state, ops[:len(ops) // 2], batch_size=batch_size)

    monkeypatch.setattr(scheduler, "apply_ops", half)


@pytest.mark.parametrize("cell,fault", [
    ("g500-served.cold", _alter("_VFULL")),
    ("g500-served.hot", _alter("_VDELTA")),
    ("g500-served.hot", _unchanged_state),
    ("g500-served.cold", _unchanged_state),
    ("g500-served.hot", _half_batch),
    ("paper-rmat.ingest", _unchanged_state),
    ("paper-rmat.ingest", _half_batch),
])
def test_broken_timed_path_is_not_correct(cell, fault, monkeypatch,
                                          no_compile_cache):
    fault(monkeypatch)
    out = run_tiny(cell)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("cell", ["g500-served.hot", "g500-served.cold",
                                  "paper-rmat.ingest"])
def test_control_fails_the_limits(cell, no_compile_cache):
    out = run_tiny(cell, control=reference.to_bfloat16)
    assert out["correct"]
    ctrl = out["control"]
    assert any(v > check.LIMITS[k] for k, v in ctrl.items()), ctrl


def test_without_a_tpu_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "bench", "run.py"),
                        "--workload", "g500-served.hot", "--seed", "1",
                        "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"correct"' not in r.stdout


def test_peaks_table():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v99")


def test_benchmark_json_resolves_and_keeps_to_the_contract():
    bench = harness.load_benchmark()
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert 1 <= bench["run_seconds"] <= 51
    assert os.path.join(*bench["command"][1].split("/")).startswith("bench")
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"] == f"bench/configs/{c['name']}.json"
        cfg = graphs.load_config(c["name"])
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(k in cfg for k in c["reduced"])
        for text in (c["source"], c["why"]):
            assert 1 <= len(text) <= 200 and "\n" not in text
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 2)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        workload.load_traffic(w["traffic"])
        used.add(w["config"])
        reported = {m["name"] for m in harness.metrics_for(bench, w["name"],
                                                           False)}
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.metrics_for(bench, w["name"], True)
    assert used == {c["name"] for c in bench["configs"]}
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert callable(harness.load_reader(m["name"]))
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert m["moves"] in {x["name"] for x in harness.metrics_for(
                bench, cell, False)}


def test_replay_batch_semantics():
    """Vertex ops of a batch before its edge ops; a vertex removed in a
    batch loses its edges even if the batch adds it back."""
    PUTV, REMV, PUTE, REME = (reference.PUTV, reference.REMV,
                              reference.PUTE, reference.REME)
    n = 4
    base = reference.HostGraph.from_edges(
        n, np.array([0, 1, 2]), np.array([1, 2, 3]),
        np.array([1.0, 2.0, 3.0], np.float32))
    ops = [(PUTE, 0, 2, 5.0),      # batch 0: edge op first in order...
           (REMV, 2),              # ...but the removal applies first
           (PUTV, 2),
           (REME, 0, 1),
           (PUTE, 3, 0, 7.0),      # batch 1
           (PUTE, 0, 2, 9.0)]
    g = reference.replay(base, ops, 4, 4)
    assert g.alive.tolist() == [True, True, True, True]
    # 1->2 and 2->3 died with vertex 2; 0->2 added after; 0->1 removed
    assert g.keys.tolist() == [0 * n + 2]
    g = reference.replay(base, ops, 6, 4)
    assert g.keys.tolist() == [0 * n + 2, 3 * n + 0]
    assert g.w.tolist() == [9.0, 7.0]


def test_generation_is_fixed_by_the_seed():
    cfg = graphs.load_config("g500-served")
    cfg["scale"] = 8
    a = graphs.generate(cfg, SEED)
    b = graphs.generate(cfg, SEED)
    c = graphs.generate(cfg, SEED + 1)
    assert all(np.array_equal(x, y) for x, y in zip(a[2:], b[2:]))
    assert not np.array_equal(a[2], c[2])
    assert a[1] == int(2 * 16 * 256 * 1.5)           # arcs both ways


def test_fixed_structure_gives_every_seed_the_same_work():
    """Under a fixed structure seed, two seeds serve one graph under other
    labels, and the hot pool and churn hot sets are the same vertices of
    it; the clients' sequences differ."""
    cfg = graphs.load_config("g500-served")
    cfg["scale"] = 8
    traffic = workload.load_traffic("hot")
    seen = []
    for seed in (SEED, SEED + 1):
        n, _, src, dst, w, labels = graphs.generate(cfg, seed)
        g = reference.HostGraph.from_edges(n, src, dst, w)
        p = workload.plan(traffic, g, seed, 1.0, cfg["weight_max"], 0,
                          labels=labels,
                          structure=graphs.structure_seed(cfg, seed),
                          directed=cfg["directed"])
        back = np.argsort(labels)           # label -> structural vertex
        edges = sorted(zip(back[g.keys // n].tolist(),
                           back[g.keys % n].tolist(), g.w.tolist()))
        hot = [back[op[1]] for op in p.updates[:32:2]]
        seen.append((edges, {k: back[v].tolist() for k, v in
                             p.pools.items()}, hot, p.clients[0][1][:50],
                     labels))
    (e0, p0, h0, c0, l0), (e1, p1, h1, c1, l1) = seen
    assert e0 == e1 and p0 == p1
    assert set(h0) & set(h1)
    assert not np.array_equal(l0, l1) and c0 != c1
    # fresh sources: the same structural vertices, in the same order
    cold = workload.load_traffic("cold")
    asked = []
    for seed, labels in ((SEED, l0), (SEED + 1, l1)):
        n, _, src, dst, w, _ = graphs.generate(cfg, seed)
        g = reference.HostGraph.from_edges(n, src, dst, w)
        p = workload.plan(cold, g, seed, 1.0, cfg["weight_max"], 0,
                          labels=labels,
                          structure=graphs.structure_seed(cfg, seed),
                          directed=cfg["directed"])
        back = np.argsort(labels)
        asked.append([back[s].tolist() for _, ss in p.clients for s in ss])
    assert asked[0] == asked[1]


def test_undirected_graph_and_churn_stay_symmetric():
    """An undirected configuration loads every tuple both ways with one
    weight, and its churn keeps every committed version symmetric, with no
    delete of a missing edge."""
    cfg = graphs.load_config("g500-served")
    assert cfg["directed"] is False
    cfg["scale"] = 8
    n, ecap, src, dst, w, labels = graphs.generate(cfg, SEED)
    g = reference.HostGraph.from_edges(n, src, dst, w)
    assert g.keys.size == src.size and ecap >= g.keys.size

    def arcs(h):
        return dict(zip(zip((h.keys // n).tolist(), (h.keys % n).tolist()),
                        h.w.tolist()))

    def symmetric(h):
        e = arcs(h)
        return all(e.get((v, u)) == x for (u, v), x in e.items())

    assert symmetric(g) and not any(u == v for u, v in arcs(g))
    traffic = workload.load_traffic("cold")
    p = workload.plan(traffic, g, SEED, 32.0, cfg["weight_max"], 0,
                      labels=labels, structure=graphs.structure_seed(cfg, SEED),
                      directed=False)
    ops = p.updates
    assert len(ops) >= 64
    live = arcs(g)
    for i in range(0, len(ops) - 1, 2):
        a, b = ops[i], ops[i + 1]
        assert a[0] == b[0] and (a[1], a[2]) == (b[2], b[1]) and a[1] != a[2]
        if a[0] == reference.REME:
            assert (a[1], a[2]) in live
            live.pop((a[1], a[2])), live.pop((a[2], a[1]))
        else:
            live[(a[1], a[2])] = live[(a[2], a[1])] = a[3]
    for k in range(32, len(ops) + 1, 32):
        assert symmetric(reference.replay(g, ops, k, 32))


def test_update_rate_counts_the_commit_running_at_the_close():
    """A commit that straddles the window's end counts with its ops and its
    time, so a stall there slows the rate; without one the span is the
    window.  A metric split by cell reads with its stem's reader."""
    rate = harness.load_reader("update_ops_per_s")
    fresh = harness.load_reader("fresh_p95_ms")
    assert harness.load_reader("fresh_p95_ms.hot").__module__ != \
        rate.__module__

    def commit(t_call, t_ret):
        return harness.CommitRec(t_call, t_ret, 0, 0, [t_call] * 32)

    run = harness.Run("paper-rmat.ingest", 10.0, 1.0, t0=100.0,
                      commits=[commit(100.0 + i, 101.0 + i)
                               for i in range(9)])
    assert rate(run) == pytest.approx(9 * 32 / 10.0)
    run.closing = commit(109.0, 110.5)
    assert rate(run) == pytest.approx(10 * 32 / 10.5)
    run.closing = commit(109.0, 130.0)            # a stall at the close
    assert rate(run) == pytest.approx(10 * 32 / 30.0)
    assert fresh(run) == pytest.approx(21e3)
