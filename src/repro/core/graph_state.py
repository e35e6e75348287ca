"""Versioned, fixed-capacity, functional dynamic-graph state.

TPU-native adaptation of PANIGRAHAM's composite data structure (lock-free
hash-table of VNodes + lock-free BST edge-lists):

  * The vertex "hash table" is a direct-indexed table of capacity ``vcap``:
    ``alive[v]`` (vertex liveness), ``ecnt[v]`` (the paper's per-vertex edge
    version counter, bumped on every edge mutation incident at ``v``) and a
    global ``version`` (bumped once per committed update batch -- each batch
    commit is a linearization boundary).
  * The per-vertex BST edge-lists become ONE lexicographically sorted
    ``(src, dst)`` key array with slack capacity ``ecap``.  Binary search over
    the sorted pairs (``pair_searchsorted``) is the vectorized analogue of the
    BST's O(log E) descent, applied to whole update batches at once.
  * The paper's *logical removal* (pointer marking / bit stealing) maps to
    weight tombstones: a removed edge keeps its key slot (preserving the sort
    invariant, exactly like a marked-but-not-unlinked BST node) with
    ``weight = +inf``.  ``compact`` is the physical unlink ("helping").
  * Empty slots carry the sentinel key ``(NOKEY, NOKEY)`` which sorts last, so
    the array is totally sorted at full capacity at all times.

All operations are pure: they take a ``GraphState`` and return a new one.
A new state with a bumped ``version`` is a new MVCC snapshot -- the paper's
CAS-committed heap mutation becomes a value commit.
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# Sentinel for empty edge slots / invalid vertex ids.  Must be the maximum
# int32 so empty slots sort after every real key.
NOKEY: int = 2**31 - 1
# Weight tombstone: logically-removed edge (and "no edge" in dense form).
# A host scalar, so importing the package starts no JAX backend.
INF = np.float32(np.inf)


class GraphState(NamedTuple):
    """A committed snapshot of the dynamic graph. All fields are arrays."""

    # --- vertex table (the "hash table") ---
    alive: jax.Array      # bool[vcap]   vertex liveness
    ecnt: jax.Array       # int32[vcap]  per-vertex edge version counter
    # --- edge table (the composed "BSTs"), lexicographically sorted ---
    esrc: jax.Array       # int32[ecap]  source vertex id (NOKEY = empty slot)
    edst: jax.Array       # int32[ecap]  destination vertex id
    ew: jax.Array         # f32[ecap]    weight; +inf = logically removed
    # --- global MVCC version, one bump per committed batch ---
    version: jax.Array    # int32[] scalar

    @property
    def vcap(self) -> int:
        return self.alive.shape[0]

    @property
    def ecap(self) -> int:
        return self.esrc.shape[0]


def make_graph(vcap: int, ecap: int) -> GraphState:
    """An empty graph with capacity for ``vcap`` vertices and ``ecap`` edges."""
    return GraphState(
        alive=jnp.zeros((vcap,), jnp.bool_),
        ecnt=jnp.zeros((vcap,), jnp.int32),
        esrc=jnp.full((ecap,), NOKEY, jnp.int32),
        edst=jnp.full((ecap,), NOKEY, jnp.int32),
        ew=jnp.full((ecap,), INF, jnp.float32),
        version=jnp.zeros((), jnp.int32),
    )


# ---------------------------------------------------------------------------
# Sorted-pair binary search: the vectorized BST descent.
# ---------------------------------------------------------------------------

def _pair_less(a_src, a_dst, b_src, b_dst):
    return (a_src < b_src) | ((a_src == b_src) & (a_dst < b_dst))


def pair_searchsorted(esrc: jax.Array, edst: jax.Array,
                      qu: jax.Array, qv: jax.Array) -> jax.Array:
    """Leftmost index where ``(esrc, edst) >= (qu, qv)``, vectorized over q.

    ``(esrc, edst)`` must be lexicographically sorted (empty slots = NOKEY
    sort last).  int32-only -- no 64-bit composite keys needed.
    """
    ecap = esrc.shape[0]
    steps = max(1, int(math.ceil(math.log2(max(ecap, 2)))) + 1)
    lo = jnp.zeros(qu.shape, jnp.int32)
    hi = jnp.full(qu.shape, ecap, jnp.int32)

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        midc = jnp.clip(mid, 0, ecap - 1)
        less = _pair_less(esrc[midc], edst[midc], qu, qv)
        return jnp.where(less, mid + 1, lo), jnp.where(less, hi, mid)

    lo, hi = lax.fori_loop(0, steps, body, (lo, hi))
    return lo


def find_edge_slots(state: GraphState, qu: jax.Array, qv: jax.Array):
    """Locate edge keys. Returns ``(idx, key_present, live)``.

    ``key_present``: the key occupies a slot (live or tombstoned).
    ``live``: key present AND not logically removed AND both endpoints alive.
    """
    idx = pair_searchsorted(state.esrc, state.edst, qu, qv)
    idxc = jnp.clip(idx, 0, state.ecap - 1)
    key_present = (state.esrc[idxc] == qu) & (state.edst[idxc] == qv) & (qu != NOKEY)
    quc = jnp.clip(qu, 0, state.vcap - 1)
    qvc = jnp.clip(qv, 0, state.vcap - 1)
    live = key_present & (state.ew[idxc] < INF) & state.alive[quc] & state.alive[qvc]
    return idxc, key_present, live


# ---------------------------------------------------------------------------
# Derived views & maintenance.
# ---------------------------------------------------------------------------

def live_edge_mask(state: GraphState) -> jax.Array:
    """bool[ecap]: slots holding a live (unmarked, endpoints-alive) edge."""
    src_ok = state.alive[jnp.clip(state.esrc, 0, state.vcap - 1)]
    dst_ok = state.alive[jnp.clip(state.edst, 0, state.vcap - 1)]
    return (state.esrc != NOKEY) & (state.ew < INF) & src_ok & dst_ok


def num_vertices(state: GraphState) -> jax.Array:
    return jnp.sum(state.alive.astype(jnp.int32))


def num_edges(state: GraphState) -> jax.Array:
    return jnp.sum(live_edge_mask(state).astype(jnp.int32))


def used_slots(state: GraphState) -> jax.Array:
    """Occupied slots (live + tombstones)."""
    return jnp.sum((state.esrc != NOKEY).astype(jnp.int32))


@jax.jit
def compact(state: GraphState) -> GraphState:
    """Physically remove tombstoned edges (the paper's unlink/"helping").

    A stable sort by the removed-flag keeps live entries in lexicographic
    order and pushes tombstones (converted to empty slots) to the end.
    """
    removed = (state.ew >= INF) | (state.esrc == NOKEY)
    order = jnp.argsort(removed, stable=True)
    esrc = jnp.where(removed[order], NOKEY, state.esrc[order])
    edst = jnp.where(removed[order], NOKEY, state.edst[order])
    ew = jnp.where(removed[order], INF, state.ew[order])
    return state._replace(esrc=esrc, edst=edst, ew=ew)


def grow_edges(state: GraphState, factor: int = 2) -> GraphState:
    """Reallocate the edge table with more slack (the paper's RESIZE grow)."""
    extra = state.ecap * (factor - 1)
    return state._replace(
        esrc=jnp.concatenate([state.esrc, jnp.full((extra,), NOKEY, jnp.int32)]),
        edst=jnp.concatenate([state.edst, jnp.full((extra,), NOKEY, jnp.int32)]),
        ew=jnp.concatenate([state.ew, jnp.full((extra,), INF, jnp.float32)]),
    )


def grow_vertices(state: GraphState, factor: int = 2) -> GraphState:
    """Reallocate the vertex table (RESIZE grow for the hash table)."""
    extra = state.vcap * (factor - 1)
    return state._replace(
        alive=jnp.concatenate([state.alive, jnp.zeros((extra,), jnp.bool_)]),
        ecnt=jnp.concatenate([state.ecnt, jnp.zeros((extra,), jnp.int32)]),
    )


@jax.jit
def densify(state: GraphState) -> jax.Array:
    """Dense weight matrix ``W[f32, vcap x vcap]``; +inf = no edge.

    This is the bridge to the MXU path: batched semiring queries (and the
    Pallas kernels) operate on dense tiles derived from a snapshot.
    """
    live = live_edge_mask(state)
    srcc = jnp.where(live, state.esrc, 0)
    dstc = jnp.where(live, state.edst, 0)
    w = jnp.full((state.vcap, state.vcap), INF, jnp.float32)
    vals = jnp.where(live, state.ew, INF)
    return w.at[srcc, dstc].min(vals, mode="drop")


def from_edge_list(vcap: int, ecap: int, src, dst, w=None) -> GraphState:
    """Build a committed graph from host edge arrays (bulk load)."""
    src = np.asarray(src, np.int32)
    dst = np.asarray(dst, np.int32)
    if w is None:
        w = np.ones_like(src, np.float32)
    w = np.asarray(w, np.float32)
    # dedup, keep last weight
    keys = src.astype(np.int64) * np.int64(vcap) + dst.astype(np.int64)
    order = np.argsort(keys, kind="stable")
    keys, src, dst, w = keys[order], src[order], dst[order], w[order]
    last = np.ones(len(keys), bool)
    last[:-1] = keys[:-1] != keys[1:]
    src, dst, w = src[last], dst[last], w[last]
    n = len(src)
    if n > ecap:
        raise ValueError(f"edge capacity {ecap} < {n} edges")
    esrc = np.full((ecap,), NOKEY, np.int32)
    edst = np.full((ecap,), NOKEY, np.int32)
    ew = np.full((ecap,), np.inf, np.float32)
    esrc[:n], edst[:n], ew[:n] = src, dst, w
    alive = np.zeros((vcap,), bool)
    touched = np.unique(np.concatenate([src, dst]))
    alive[touched] = True
    return GraphState(
        alive=jnp.asarray(alive),
        ecnt=jnp.zeros((vcap,), jnp.int32),
        esrc=jnp.asarray(esrc),
        edst=jnp.asarray(edst),
        ew=jnp.asarray(ew),
        version=jnp.zeros((), jnp.int32),
    )
