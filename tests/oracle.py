"""Pure-python reference model of the paper's ADT (the sequential oracle),
and helpers that build an engine graph and its oracle from one op list."""
from __future__ import annotations

import math
from collections import deque

from repro.core import PUTE, PUTV, REME, REMV, apply_ops, make_graph

INF = float("inf")


class GraphOracle:
    """Sequential directed graph with the exact ADT semantics of Section 2."""

    def __init__(self):
        self.vertices: set[int] = set()
        self.edges: dict[tuple[int, int], float] = {}

    # --- updates -----------------------------------------------------
    def put_v(self, v):
        if v in self.vertices:
            return False
        self.vertices.add(v)
        return True

    def rem_v(self, v):
        if v not in self.vertices:
            return False
        self.vertices.discard(v)
        self.edges = {(a, b): w for (a, b), w in self.edges.items()
                      if a != v and b != v}
        return True

    def get_v(self, v):
        return v in self.vertices

    def put_e(self, u, v, w):
        if u not in self.vertices or v not in self.vertices:
            return False, INF
        if (u, v) in self.edges:
            old = self.edges[(u, v)]
            if old == w:
                return False, old
            self.edges[(u, v)] = w
            return True, old
        self.edges[(u, v)] = w
        return True, INF

    def rem_e(self, u, v):
        if (u, v) in self.edges and u in self.vertices and v in self.vertices:
            return True, self.edges.pop((u, v))
        return False, INF

    def get_e(self, u, v):
        if (u, v) in self.edges and u in self.vertices and v in self.vertices:
            return True, self.edges[(u, v)]
        return False, INF

    # --- queries -----------------------------------------------------
    def adj(self):
        out = {}
        for (u, v), w in self.edges.items():
            if u in self.vertices and v in self.vertices:
                out.setdefault(u, []).append((v, w))
        return out

    def bfs(self, src):
        if src not in self.vertices:
            return None
        adj = self.adj()
        dist = {src: 0}
        q = deque([src])
        while q:
            u = q.popleft()
            for v, _ in sorted(adj.get(u, [])):
                if v not in dist:
                    dist[v] = dist[u] + 1
                    q.append(v)
        return dist

    def sssp(self, src):
        """Bellman-Ford. Returns (dist dict, negcycle flag)."""
        if src not in self.vertices:
            return None, False
        adj = self.adj()
        dist = {v: INF for v in self.vertices}
        dist[src] = 0.0
        for _ in range(max(1, len(self.vertices) - 1)):
            changed = False
            for u, nbrs in adj.items():
                if dist.get(u, INF) == INF:
                    continue
                for v, w in nbrs:
                    if dist[u] + w < dist[v] - 1e-9:
                        dist[v] = dist[u] + w
                        changed = True
            if not changed:
                break
        neg = False
        for u, nbrs in adj.items():
            if dist.get(u, INF) == INF:
                continue
            for v, w in nbrs:
                if dist[u] + w < dist[v] - 1e-6:
                    neg = True
        return dist, neg

    def bc_dependencies(self, src):
        """Brandes single-source dependencies delta(src | v)."""
        if src not in self.vertices:
            return None
        adj = self.adj()
        # forward
        dist = {src: 0}
        sigma = {src: 1.0}
        order = []
        q = deque([src])
        while q:
            u = q.popleft()
            order.append(u)
            for v, _ in adj.get(u, []):
                if v not in dist:
                    dist[v] = dist[u] + 1
                    sigma[v] = 0.0
                    q.append(v)
                if dist[v] == dist[u] + 1:
                    sigma[v] += sigma[u]
        delta = {v: 0.0 for v in dist}
        for u in reversed(order):
            for v, _ in adj.get(u, []):
                if dist.get(v, -9) == dist[u] + 1:
                    delta[u] += sigma[u] / sigma[v] * (1 + delta[v])
        delta[src] = 0.0
        return delta

    def bc_scores(self):
        """Exact all-sources betweenness: BC(v) = sum_s delta(s | v)."""
        scores = {v: 0.0 for v in self.vertices}
        for s in self.vertices:
            for v, d in self.bc_dependencies(s).items():
                scores[v] += d
        return scores


def apply_pair(g, o, ops, batch_size=None):
    """Commit ``ops`` to ``g`` as one batch and replay them on the oracle
    ``o`` in the batch's linearization order: vertex ops, then edge ops."""
    g, _ = apply_ops(g, ops, batch_size)
    step = {PUTV: o.put_v, REMV: o.rem_v, PUTE: o.put_e, REME: o.rem_e}
    for kind, *args in sorted(ops, key=lambda op: op[0] in (PUTE, REME)):
        step[kind](*args)
    return g, o


def build_pair(n, edges, vcap=None, ecap=None, batch_size=None):
    """Vertices ``0..n-1`` and the weighted arcs ``edges``, committed to a
    fresh graph (capacities sized to fit unless given) and to an oracle."""
    g = make_graph(vcap or max(16, n), ecap or max(16, 4 * len(edges)))
    ops = [(PUTV, v) for v in range(n)]
    ops += [(PUTE, u, v, w) for u, v, w in edges]
    return apply_pair(g, GraphOracle(), ops, batch_size)
