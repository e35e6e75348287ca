"""The one general traffic generator: a mix's JSON file in, a plan out.

A plan fixes, from the seed, everything the window will send: each query
client's source sequence, the pool the result cache is filled from, and
the stream of update ops.  Keys of a mix file:

  * ``clients``: query clients per kind (closed loop, one request in
    flight each);
  * ``sources``: ``{"policy": "pool", "pool_size", "zipf", "prefill"}``
    (each kind's clients draw from a fixed pool of vertices with
    out-degree > 0 by Zipf popularity of rank) or ``{"policy": "fresh"}``
    (every query a vertex with out-degree > 0 never asked before);
  * ``churn``: open-loop skewed edge churn at ``rate_ops_per_s``: a hot
    set of ``hot_set`` vertices that moves every ``hot_every`` ops, Zipf
    ``zipf`` within it; ``insert`` / ``delete`` / ``reweight`` shares (in
    an undirected configuration an edge update is two ops, one per arc);
  * ``writer``: one closed-loop writer with ``ops`` shares of ``putv``,
    ``remv``, ``pute``, ``reme`` over uniform endpoints, up to
    ``max_ops_per_s`` times the window;
  * ``warm_rungs``: the ladder rungs the mix's queries ride, warmed up at
    every lane count up to the clients per kind.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from .graphs import rng_for
from .reference import PUTE, PUTV, REME, REMV, HostGraph

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "traffic")
#: a pool client's pre-drawn sequence (more than any window can ask)
POOL_DRAWS = 20000


def load_traffic(name: str) -> dict:
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        return json.load(f)


@dataclass
class Plan:
    clients: List[tuple] = field(default_factory=list)  # (kind, sources)
    pools: Dict[str, list] = field(default_factory=dict)
    updates: list = field(default_factory=list)         # op tuples
    open_loop_rate: float = 0.0                         # 0: closed loop


def _zipf(k: int, s: float) -> np.ndarray:
    p = 1.0 / np.arange(1, k + 1) ** s
    return p / p.sum()


def plan(traffic: dict, g: HostGraph, seed: int, seconds: float,
         weight_max: int, extra_ops: int, labels: np.ndarray,
         structure: int, directed: bool) -> Plan:
    """The whole traffic of one run; ``extra_ops`` update ops go before the
    window's (the set-up's warm-up commits take them).

    What sets the size of the work, the pool, the fresh sources and the
    churn's hot sets, is drawn from the structure's seed ``structure`` over
    the vertices in structural order (``labels[i]`` is the label of
    structural vertex ``i``), so that a fixed structure gives every seed the
    same work in another order.  Pool clients' draws and update targets
    come from ``seed``.
    """
    out = Plan()
    ok = g.alive & (g.out_degree() > 0)
    eligible = labels[ok[labels]]
    rng = rng_for(seed, 1)
    src = traffic.get("sources", {})
    kinds = [k for k, c in traffic.get("clients", {}).items() for _ in
             range(c)]
    if src.get("policy") == "pool":
        pick = rng_for(structure, 5)
        for kind in traffic["clients"]:
            out.pools[kind] = [int(x) for x in pick.choice(
                eligible, src["pool_size"], replace=False)]
        p = _zipf(src["pool_size"], src["zipf"])
        for kind in kinds:
            draws = rng.choice(src["pool_size"], POOL_DRAWS, p=p)
            out.clients.append((kind, [out.pools[kind][i] for i in draws]))
    elif src.get("policy") == "fresh":
        # the same structural vertices in the same order on every seed
        order = [int(x) for x in rng_for(structure, 7).permutation(eligible)]
        for i, kind in enumerate(kinds):
            out.clients.append((kind, order[i::len(kinds)]))
    if "churn" in traffic:
        c = traffic["churn"]
        out.open_loop_rate = float(c["rate_ops_per_s"])
        n_ops = extra_ops + int(np.ceil(out.open_loop_rate * seconds))
        out.updates = churn(g, rng_for(seed, 2), n_ops, c, weight_max,
                            rng_for(structure, 6).permutation(eligible),
                            directed)
    elif "writer" in traffic:
        wr = traffic["writer"]
        n_ops = extra_ops + int(wr["max_ops_per_s"] * seconds)
        out.updates = uniform_updates(g.n, rng_for(seed, 2), n_ops,
                                      wr["ops"], weight_max)
    return out


def churn(g: HostGraph, rng, n_ops: int, c: dict, weight_max: int,
          movers: np.ndarray, directed: bool) -> list:
    """Skewed edge churn: per ``hot_every`` ops a new hot set of
    ``hot_set`` vertices (the next slice of ``movers``, in their order),
    sources drawn Zipf within it; an insert goes to a random
    alive vertex, a delete or re-weight to one of the source's current
    out-neighbours (an insert when it has none).  In an undirected graph
    each edge update is two ops, one per arc, next to each other.  The
    out-lists of touched vertices are tracked as ops go, so a delete always
    names a live edge.
    """
    alive = np.flatnonzero(g.alive)
    p = _zipf(c["hot_set"], c["zipf"])
    adj: Dict[int, dict] = {}

    def out(x: int) -> dict:
        if x not in adj:
            dst, w = g.out_edges(x)
            adj[x] = dict(zip(dst.tolist(), w.tolist()))
        return adj[x]

    arcs = (lambda u, v: [(u, v)]) if directed else (
        lambda u, v: [(u, v), (v, u)])
    per = c["hot_every"] // (1 if directed else 2)
    ops = []
    us: np.ndarray = np.zeros(0, np.int64)
    i = 0
    while len(ops) < n_ops:
        j = i % per
        if j == 0:
            r = i // per
            hot = np.take(movers, np.arange(r * c["hot_set"],
                                            (r + 1) * c["hot_set"]),
                          mode="wrap")
            us = hot[rng.choice(c["hot_set"], per, p=p)]
        i += 1
        u = int(us[j])
        nbrs = out(u)
        r = rng.random()
        if r < c["insert"] or not nbrs:
            v = int(alive[rng.integers(alive.size)])
            while v == u and not directed:      # no self loop both ways
                v = int(alive[rng.integers(alive.size)])
            w = float(rng.integers(1, weight_max + 1))
            for a, b in arcs(u, v):
                ops.append((PUTE, a, b, w))
                out(a)[b] = w
        elif r < c["insert"] + c["delete"]:
            v = list(nbrs)[rng.integers(len(nbrs))]
            for a, b in arcs(u, v):
                ops.append((REME, a, b))
                out(a).pop(b, None)
        else:
            v = list(nbrs)[rng.integers(len(nbrs))]
            w = float(rng.integers(1, weight_max + 1))
            for a, b in arcs(u, v):
                ops.append((PUTE, a, b, w))
                out(a)[b] = w
    return ops[:n_ops]


def uniform_updates(n: int, rng, n_ops: int, shares: dict,
                    weight_max: int) -> list:
    """The update half of the paper's mix: PutV / RemV / PutE / RemE by
    ``shares``, endpoints uniform over all ``n`` vertex slots."""
    names = ("putv", "remv", "pute", "reme")
    p = np.array([shares[k] for k in names], np.float64)
    kind = rng.choice(4, n_ops, p=p / p.sum())
    u = rng.integers(0, n, n_ops)
    v = rng.integers(0, n, n_ops)
    w = rng.integers(1, weight_max + 1, n_ops).astype(np.float64)
    codes = (PUTV, REMV, PUTE, REME)
    ops = []
    for k, a, b, x in zip(kind.tolist(), u.tolist(), v.tolist(), w.tolist()):
        if k < 2:
            ops.append((codes[k], a))
        elif k == 2:
            ops.append((PUTE, a, b, x))
        else:
            ops.append((REME, a, b))
    return ops
