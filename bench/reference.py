"""Plain host reference: the graph as sorted numpy keys, its versions, and
textbook BFS / SSSP / Brandes.  Imports nothing of the program.

Op codes are the ADT's (``PUTV``..``REME``); the harness checks that they
equal the program's before a run.  Semantics follow the program's
documented contract: a committed batch applies its vertex ops first, in
submission order, then its edge ops in submission order; an edge op with a
dead endpoint does nothing; a vertex removed at any point of a batch loses
every incident edge, even if the same batch adds it back.
"""
from __future__ import annotations

import numpy as np
from scipy.sparse import csr_matrix
from scipy.sparse.csgraph import dijkstra

NOP, PUTV, REMV, PUTE, REME = range(5)


class HostGraph:
    """Sorted unique ``u * n + v`` keys with their weights, plus liveness."""

    def __init__(self, n: int, keys: np.ndarray, w: np.ndarray,
                 alive: np.ndarray):
        self.n, self.keys, self.w, self.alive = n, keys, w, alive
        self._csr = None

    @classmethod
    def from_edges(cls, n: int, src, dst, w) -> "HostGraph":
        keys = src.astype(np.int64) * n + dst.astype(np.int64)
        # duplicate keys keep the last weight (the bulk load's rule)
        rev_keys, first = np.unique(keys[::-1], return_index=True)
        weights = w[::-1][first].astype(np.float32)
        alive = np.zeros(n, bool)
        alive[src] = True
        alive[dst] = True
        return cls(n, rev_keys, weights, alive)

    def out_edges(self, u: int):
        """``(dst, w)`` of ``u``'s out-edges."""
        lo, hi = np.searchsorted(self.keys, [u * self.n, (u + 1) * self.n])
        return self.keys[lo:hi] - u * self.n, self.w[lo:hi]

    def out_degree(self) -> np.ndarray:
        return np.bincount(self.keys // self.n, minlength=self.n)

    def csr(self) -> csr_matrix:
        if self._csr is None:
            u = self.keys // self.n
            indptr = np.searchsorted(u, np.arange(self.n + 1))
            self._csr = csr_matrix(
                (self.w.astype(np.float64),
                 (self.keys % self.n).astype(np.int64), indptr),
                shape=(self.n, self.n))
        return self._csr


def replay(base: HostGraph, ops, n_ops: int, batch_size: int) -> HostGraph:
    """``base`` after the first ``n_ops`` of ``ops``, committed in batches
    of ``batch_size`` (vertex ops, then edge ops, per batch).

    Only the touched keys are walked in Python; base edges survive unless
    an op rewrote them or an endpoint was removed.  Times order events:
    a removal in batch ``b`` is ``2b``, an edge write in batch ``b`` is
    ``2b + 1``, the base edges are ``-1``; an edge is live iff it was
    written after both endpoints' last removal.
    """
    n = base.n
    alive = base.alive.copy()
    death = np.full(n, -2, np.int64)
    over = {}                                  # key -> (weight or None, t)
    for b, start in enumerate(range(0, n_ops, batch_size)):
        chunk = ops[start:min(start + batch_size, n_ops)]
        for op in chunk:
            if op[0] == PUTV:
                alive[op[1]] = True
            elif op[0] == REMV and alive[op[1]]:
                alive[op[1]] = False
                death[op[1]] = 2 * b
        for op in chunk:
            if op[0] in (PUTE, REME) and alive[op[1]] and alive[op[2]]:
                over[int(op[1]) * n + int(op[2])] = (
                    float(op[3]) if op[0] == PUTE else None, 2 * b + 1)
    bu, bv = base.keys // n, base.keys % n
    keep = (death[bu] == -2) & (death[bv] == -2)
    ok = np.fromiter(over, np.int64, len(over))
    ow = np.array([np.nan if x is None else x for x, _ in over.values()],
                  np.float64)
    ot = np.fromiter((t for _, t in over.values()), np.int64, len(over))
    if ok.size:
        keep &= ~np.isin(base.keys, ok)
    live = (~np.isnan(ow) & (ot > death[ok // n]) & (ot > death[ok % n])
            if ok.size else np.zeros(0, bool))
    keys = np.concatenate([base.keys[keep], ok[live]])
    w = np.concatenate([base.w[keep], ow[live].astype(np.float32)])
    order = np.argsort(keys, kind="stable")
    return HostGraph(n, keys[order], w[order], alive)


def ref_hops(g: HostGraph, s: int) -> np.ndarray:
    """BFS hop counts from ``s`` (-1 = unreached)."""
    d = dijkstra(g.csr(), directed=True, indices=s, unweighted=True)
    return np.where(np.isinf(d), -1, d).astype(np.int64)


def ref_sssp(g: HostGraph, s: int) -> np.ndarray:
    """Shortest-path distances from ``s`` (+inf = unreached)."""
    return dijkstra(g.csr(), directed=True, indices=s)


def ref_brandes(g: HostGraph, s: int, rounding=None):
    """Level-synchronous Brandes from ``s``: ``(level, sigma, delta)``.

    ``rounding`` (the control) rounds sigma and delta to a lower precision
    after every level, as a program that kept them in that precision
    would; ``None`` keeps float64.
    """
    rnd = (lambda x: x) if rounding is None else rounding
    a = g.csr()
    n = g.n
    level = np.full(n, -1, np.int64)
    sigma = np.zeros(n, np.float64)
    level[s], sigma[s] = 0, 1.0
    layers = []                       # per level: (edge src, edge dst)
    front = np.array([s], np.int64)
    d = 0
    while front.size:
        counts = a.indptr[front + 1] - a.indptr[front]
        us = np.repeat(front, counts)
        starts = np.repeat(a.indptr[front] - np.cumsum(counts) + counts,
                           counts)
        vs = a.indices[starts + np.arange(us.size)].astype(np.int64)
        fresh = vs[level[vs] < 0]
        level[fresh] = d + 1
        tree = level[vs] == d + 1
        us, vs = us[tree], vs[tree]
        sigma = rnd(sigma + np.bincount(vs, weights=sigma[us], minlength=n))
        layers.append((us, vs))
        front = np.unique(fresh)
        d += 1
    delta = np.zeros(n, np.float64)
    for us, vs in reversed(layers):
        delta = rnd(delta + np.bincount(
            us, weights=sigma[us] / sigma[vs] * (1.0 + delta[vs]),
            minlength=n))
    delta[s] = 0.0
    return level, sigma, delta


def to_bfloat16(x: np.ndarray) -> np.ndarray:
    """Round to bfloat16 and back (the control's precision for float32)."""
    import ml_dtypes
    return np.asarray(x).astype(ml_dtypes.bfloat16).astype(np.float64)
