"""ADT semantics of the batched update engine vs the sequential oracle."""
import jax
import numpy as np
import pytest

from repro.core import (
    GETE, GETV, PUTE, PUTV, REME, REMV, NOKEY, GraphState, OpResults,
    apply_batch, apply_ops, compact, from_edge_list, get_e, get_v, make_batch,
    make_graph, num_edges, num_vertices,
)
from repro.core.updates import _KILL_CHUNK, _apply_batch_counted
from oracle import GraphOracle
from updates_reference import apply_batch_reference


def apply_and_check(g, oracle, ops):
    """Apply ops both ways; compare per-op return values."""
    g, res = apply_ops(g, ops)
    ok = np.asarray(res.ok)
    val = np.asarray(res.val)
    for i, op in enumerate(ops):
        kind = op[0]
        if kind == PUTV:
            exp = oracle.put_v(op[1])
            assert ok[i] == exp, (i, op)
        elif kind == REMV:
            exp = oracle.rem_v(op[1])
            assert ok[i] == exp, (i, op)
        elif kind == PUTE:
            e_ok, e_val = oracle.put_e(op[1], op[2], op[3])
            assert ok[i] == e_ok, (i, op)
            assert val[i] == pytest.approx(e_val), (i, op)
        elif kind == REME:
            e_ok, e_val = oracle.rem_e(op[1], op[2])
            assert ok[i] == e_ok, (i, op)
            assert val[i] == pytest.approx(e_val), (i, op)
    return g


def test_vertex_ops_basic():
    g = make_graph(16, 16)
    o = GraphOracle()
    g = apply_and_check(g, o, [(PUTV, 1), (PUTV, 2), (PUTV, 1), (REMV, 3),
                               (REMV, 1)])
    assert bool(get_v(g, 2))
    assert not bool(get_v(g, 1))
    assert int(num_vertices(g)) == 1


def test_edge_ops_full_adt():
    g = make_graph(16, 32)
    o = GraphOracle()
    g = apply_and_check(g, o, [(PUTV, 0), (PUTV, 1), (PUTV, 2)])
    # 4a add-new, 4b replace, 4c same-weight, 4d missing vertex
    g = apply_and_check(g, o, [
        (PUTE, 0, 1, 2.0),     # (True, inf)
        (PUTE, 0, 1, 2.0),     # (False, 2.0) same weight
        (PUTE, 0, 1, 5.0),     # (True, 2.0)  replace
        (PUTE, 0, 9, 1.0),     # (False, inf) vertex missing
        (REME, 0, 1),          # (True, 5.0)
        (REME, 0, 1),          # (False, inf)
        (REME, 1, 2),          # (False, inf) never existed
    ])
    ok, w = get_e(g, 0, 1)
    assert not bool(ok)


def test_remv_clears_incident_edges():
    g = make_graph(8, 16)
    o = GraphOracle()
    g = apply_and_check(g, o, [(PUTV, 0), (PUTV, 1), (PUTV, 2),
                               (PUTE, 0, 1, 1.0), (PUTE, 1, 2, 1.0),
                               (PUTE, 2, 0, 1.0)])
    g = apply_and_check(g, o, [(REMV, 1)])
    # re-adding 1 must give a fresh (empty) edge list, as in the paper
    g = apply_and_check(g, o, [(PUTV, 1)])
    ok, _ = get_e(g, 0, 1)
    assert not bool(ok)
    ok, _ = get_e(g, 2, 0)
    assert bool(ok)
    assert int(num_edges(g)) == 1


def test_intra_batch_chains():
    g = make_graph(8, 16)
    o = GraphOracle()
    g = apply_and_check(g, o, [(PUTV, 0), (PUTV, 1)])
    # put/rem/put same edge inside one batch: sequential semantics
    g = apply_and_check(g, o, [
        (PUTE, 0, 1, 1.0), (REME, 0, 1), (PUTE, 0, 1, 3.0),
        (PUTE, 0, 1, 3.0), (REME, 0, 1), (REME, 0, 1),
    ])
    ok, _ = get_e(g, 0, 1)
    assert not bool(ok)


def test_ecnt_bumps_on_out_edge_mutations():
    g = make_graph(8, 16)
    g, _ = apply_ops(g, [(PUTV, 0), (PUTV, 1)])
    e0 = int(np.asarray(g.ecnt)[0])
    g, _ = apply_ops(g, [(PUTE, 0, 1, 1.0)])
    g, _ = apply_ops(g, [(PUTE, 0, 1, 2.0)])   # weight update bumps
    g, _ = apply_ops(g, [(PUTE, 0, 1, 2.0)])   # same weight: NO bump
    g, _ = apply_ops(g, [(REME, 0, 1)])
    assert int(np.asarray(g.ecnt)[0]) == e0 + 3


def test_overflow_grow_and_compact():
    g = make_graph(8, 4)
    g, _ = apply_ops(g, [(PUTV, i) for i in range(7)])
    g, res = apply_ops(g, [(PUTE, 0, i, 1.0) for i in range(1, 7)])
    assert all(np.asarray(res.ok))
    assert int(num_edges(g)) == 6
    g, _ = apply_ops(g, [(REME, 0, 1), (REME, 0, 2)])
    g = compact(g)
    assert int(num_edges(g)) == 4
    used = int((np.asarray(g.esrc) != NOKEY).sum())
    assert used == 4


def test_version_bumps_per_batch():
    g = make_graph(8, 8)
    v0 = int(g.version)
    g, _ = apply_ops(g, [(PUTV, 0)])
    g, _ = apply_ops(g, [(PUTV, 1)])
    assert int(g.version) == v0 + 2


def test_gets_linearize_at_batch_end():
    g = make_graph(8, 8)
    g, res = apply_ops(g, [(PUTV, 0), (GETV, 0), (REMV, 0), (GETV, 0)])
    ok = np.asarray(res.ok)
    # both GETVs see the post-batch state (0 removed)
    assert not ok[1] and not ok[3]


# ---------------- scatter-free commit vs its earlier formulation ----------

V_DIFF, E_DIFF, B_DIFF = 64, 256, 16


def _table(edges, vcap=V_DIFF, ecap=E_DIFF, alive=()):
    """A committed state holding ``edges`` ``(u, v, w)``; ``alive`` adds
    vertices with no edges."""
    src, dst, w = (np.array(c, dtype) for c, dtype in zip(
        zip(*edges) if edges else ((), (), ()),
        (np.int32, np.int32, np.float32)))
    g = from_edge_list(vcap, ecap, src, dst, w)
    return g._replace(alive=g.alive.at[np.asarray(alive, np.int32)].set(True))


def _random_ops(rng, n, count, kinds=(PUTV, REMV, PUTE, REME, GETV, GETE)):
    ops = []
    for kind in rng.choice(np.asarray(kinds), count):
        u, v = int(rng.integers(0, n)), int(rng.integers(0, n))
        ops.append((int(kind), u, v, float(rng.integers(1, 4))))
    return ops


def _band(lo, hi, rng, k):
    """``k`` distinct random edges with both endpoints in ``[lo, hi)``."""
    pairs = {(int(rng.integers(lo, hi)), int(rng.integers(lo, hi)))
             for _ in range(4 * k)}
    return [(u, v, float(rng.integers(1, 9))) for u, v in sorted(pairs)[:k]]


def _case_appends(where):
    def build(rng):
        g = _table(_band(16, 48, rng, 120), alive=range(V_DIFF))
        lo, hi = {"start": (0, 16), "middle": (24, 40), "end": (48, 64)}[where]
        return g, [(PUTE, int(rng.integers(lo, hi)), int(rng.integers(0, 64)),
                    float(rng.integers(1, 4))) for _ in range(B_DIFF)]
    return build


def _case_random(seed, ecap):
    def build(rng):
        rng = np.random.default_rng(seed)
        g = _table(_band(0, V_DIFF, rng, 150), ecap=ecap,
                   alive=range(0, V_DIFF, 2))
        return g, _random_ops(rng, V_DIFF + 2, B_DIFF)
    return build


def _chains(rng):
    g = _table([(1, 2, 1.0), (2, 3, 1.0), (3, 1, 2.0), (5, 2, 4.0)],
               alive=range(8))
    return g, [(PUTE, 0, 1, 1.0), (REME, 0, 1), (PUTE, 0, 1, 3.0),
               (PUTE, 0, 1, 3.0), (REMV, 2), (PUTV, 2), (PUTE, 2, 3, 5.0),
               (PUTE, 1, 2, 7.0), (REME, 3, 1), (PUTE, 3, 1, 2.0),
               (REMV, 6), (REMV, 6), (PUTV, 6), (GETE, 1, 2), (GETV, 2),
               (REME, 5, 2)]


def _hub(rng):
    """RemV of a vertex whose live edges, out and in, fill the invalidation
    loop's chunk several times over, beside a tombstone it must not count."""
    n = 4096
    edges = [(0, v, 1.0) for v in range(1, 2048)]
    edges += [(u, 0, 2.0) for u in range(2048, n)]
    edges += [(u, u + 1, 3.0) for u in range(1, 400)]
    g = _table(edges, vcap=n, ecap=8192)
    g = g._replace(ew=g.ew.at[5].set(np.inf))      # (0, 6) removed earlier
    return g, [(REMV, 0), (PUTE, 1, 0, 1.0), (PUTE, 7, 9, 1.0), (REMV, 300)]


def _remv_isolated(rng):
    g = _table(_band(0, 32, rng, 60), alive=range(V_DIFF))
    return g, [(REMV, 40), (PUTE, 3, 40, 1.0), (GETV, 40)]


def _no_remv(rng):
    g = _table(_band(0, V_DIFF, rng, 150), alive=range(V_DIFF))
    return g, _random_ops(rng, V_DIFF, B_DIFF, kinds=(PUTV, PUTE, REME, GETE))


def _overflow(extra):
    """Appends that fill the table to exactly its capacity, or one past."""
    def build(rng):
        free = 6
        g = _table([(u, v, 1.0) for u in range(16) for v in range(16)][
            :E_DIFF - free], alive=range(V_DIFF))
        return g, [(PUTE, 40, v, 1.0) for v in range(free + extra)] \
            + [(PUTE, 0, 1, 9.0)]
    return build


DIFF_CASES = {
    "appends_at_start": _case_appends("start"),
    "appends_in_middle": _case_appends("middle"),
    "appends_at_end": _case_appends("end"),
    # the last two tables are no whole number of 128-slot rows
    **{f"random_{s}": _case_random(s, ecap)
       for s, ecap in enumerate((E_DIFF, E_DIFF, 200, 333))},
    "intra_batch_chains": _chains,
    "hub_remv": _hub,
    "remv_isolated_vertex": _remv_isolated,
    "no_remv": _no_remv,
    "fills_to_capacity": _overflow(0),
    "overflows_by_one": _overflow(1),
}


def _host_killed(state, ops, res):
    """Live edges with an endpoint among the batch's successfully removed
    vertices: the count the commit reports."""
    ok = np.asarray(res.ok)
    gone = {op[1] for op, o in zip(ops, ok) if op[0] == REMV and o}
    esrc, edst = np.asarray(state.esrc), np.asarray(state.edst)
    live = (esrc != NOKEY) & np.isfinite(np.asarray(state.ew))
    return int((live & (np.isin(esrc, list(gone))
                        | np.isin(edst, list(gone)))).sum())


@pytest.mark.parametrize("case", sorted(DIFF_CASES))
def test_commit_equals_earlier_formulation(case):
    """The scatter-free ``apply_batch`` equals the per-slot-scatter merge
    it replaced in every state field, every op result and ``overflow``;
    its killed-edge count is the host's."""
    state, ops = DIFF_CASES[case](np.random.default_rng(7))
    batch = make_batch(ops, max(B_DIFF, len(ops)))
    new_state, res, overflow, killed = _apply_batch_counted(state, batch)
    ref_state, ref_res, ref_overflow = apply_batch_reference(state, batch)
    for name, a, b in zip(GraphState._fields, new_state, ref_state):
        assert a.dtype == b.dtype and np.array_equal(a, b), name
    for name, a, b in zip(OpResults._fields, res, ref_res):
        assert np.array_equal(a, b), name
    assert bool(overflow) == bool(ref_overflow)
    assert bool(overflow) == (case == "overflows_by_one")
    assert int(killed) == _host_killed(state, ops, res)
    if case == "hub_remv":
        assert int(killed) > 3 * _KILL_CHUNK
    public = apply_batch(state, batch)
    assert all(np.array_equal(a, b) for a, b in zip(
        jax.tree.leaves(public), jax.tree.leaves(
            (new_state, res, overflow))))
