"""Batched, vectorized implementations of the PANIGRAHAM ADT operations.

The paper linearizes individual CAS-built operations.  On an SPMD machine the
natural unit of mutation is a *batch*: ``apply_batch`` applies a fixed-size
array of operations in one jitted, fully-vectorized step and bumps the global
``version`` -- the commit is the batch's linearization boundary.  Within a
batch the sequential semantics are:

    1. vertex ops (PUTV / REMV) linearize first, in index order;
    2. edge ops (PUTE / REME) linearize next, in index order;
    3. reads (GETV / GETE) linearize at the end of the batch.

Per-op return values follow the paper's ADT exactly (including the
``<false, w>`` same-weight PutE case and the weight returned by RemE), and
intra-batch chains on the same key are resolved with true sequential
semantics via a sorted segment walk: because presence after an op depends
only on the op itself, an op's precondition depends only on its immediate
predecessor in the (key, index)-sorted order -- no sequential scan needed.

``ecnt[u]`` is bumped once per successful mutation of u's out-edge list
(PutE add / PutE weight-replace / RemE / incident-edge invalidation by RemV),
mirroring the paper's FetchAndAdd sites.

A commit writes one new version of the sorted edge table with streaming
passes only: the appends are merged in by a shift of the table
(``repro.kernels.shift_merge``) and RemV's incident edges are found by
comparing every slot with the removed ids.  Scatters and gathers are
bounded by the batch (or the invalidation loop's chunk), never by the
table's capacity: on a TPU they run element by element.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import operator
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .graph_state import (
    INF,
    NOKEY,
    GraphState,
    compact,
    find_edge_slots,
    grow_edges,
    pair_searchsorted,
    used_slots,
)

# Operation kinds.
NOP, PUTV, REMV, PUTE, REME, GETV, GETE = range(7)


class OpBatch(NamedTuple):
    kind: jax.Array   # int32[B]
    u: jax.Array      # int32[B]
    v: jax.Array      # int32[B]  (unused for vertex ops)
    w: jax.Array      # f32[B]    (PutE weight)


class OpResults(NamedTuple):
    ok: jax.Array     # bool[B]  boolean return of each op
    val: jax.Array    # f32[B]   weight return of edge ops (INF where n/a)


def make_batch(ops: Sequence[Tuple], size: int | None = None) -> OpBatch:
    """Host helper: list of (kind, u[, v[, w]]) tuples -> padded OpBatch."""
    import numpy as np

    size = size or len(ops)
    kind = np.zeros((size,), np.int32)
    u = np.full((size,), NOKEY, np.int32)
    v = np.full((size,), NOKEY, np.int32)
    w = np.full((size,), np.inf, np.float32)
    for i, op in enumerate(ops):
        kind[i] = op[0]
        if len(op) > 1:
            u[i] = op[1]
        if len(op) > 2:
            v[i] = op[2]
        if len(op) > 3:
            w[i] = op[3]
    return OpBatch(jnp.asarray(kind), jnp.asarray(u), jnp.asarray(v), jnp.asarray(w))


def _prev(arr, fill):
    rolled = jnp.roll(arr, 1)
    return rolled.at[0].set(fill)


#: Killed edges whose source ``ecnt`` bumps one trip of the invalidation
#: loop scatters: a commit that kills ``n`` edges runs ``ceil(n / 1024)``
#: trips, and none when no vertex was removed.
_KILL_CHUNK = 1024


def _is_any(x, keys):
    """bool, shaped like ``x``: ``x`` equals one of the few ``keys``."""
    return functools.reduce(operator.or_,
                            [x == keys[i] for i in range(keys.shape[0])])


def _bump_killed_sources(ecnt, esrc, kill, n_kill):
    """``ecnt`` with one bump at the source of every killed slot.

    The killed slots are found ``_KILL_CHUNK`` at a time: a binary search
    into the running count of kills per row of 128 slots finds each one's
    row, and a count within the gathered rows finds its lane.  Each loop
    trip scatters a chunk of sources, so the trip count is
    ``ceil(n_kill / _KILL_CHUNK)`` whatever the degree of the removed
    vertices.  The running count is over rows, not slots: a prefix sum of
    all ``ecap`` slots costs more than the rest of the commit.
    """
    vcap, ecap = ecnt.shape[0], esrc.shape[0]
    lanes = 128
    chunk = jnp.arange(1, _KILL_CHUNK + 1, dtype=jnp.int32)

    def bump(ecnt):
        rows = jnp.pad(kill, (0, -ecap % lanes)).reshape(-1, lanes)
        in_row = jnp.sum(rows, axis=1, dtype=jnp.int32)
        upto = jnp.cumsum(in_row)                   # kills up to each row's end

        def trip(carry):
            done, ecnt = carry
            q = done + chunk                        # ranks of this trip's kills
            row = jnp.minimum(jnp.searchsorted(upto, q, method="scan"),
                              rows.shape[0] - 1).astype(jnp.int32)
            k = q - upto[row] + in_row[row]         # rank within its row
            lane = jnp.sum(jnp.cumsum(rows[row], axis=1, dtype=jnp.int32)
                           < k[:, None], axis=1, dtype=jnp.int32)
            slot = jnp.minimum(row * lanes + lane, ecap - 1)
            src = jnp.where(q <= n_kill, esrc[slot], vcap)
            return done + _KILL_CHUNK, ecnt.at[src].add(1, mode="drop")

        return lax.while_loop(lambda c: c[0] < n_kill, trip,
                              (jnp.int32(0), ecnt))[1]

    return lax.cond(n_kill > 0, bump, lambda ecnt: ecnt, ecnt)


def _apply_batch(state: GraphState, ops: OpBatch):
    """``apply_batch`` with the number of edges the batch's RemVs killed."""
    from repro.kernels import shift_merge

    vcap, ecap = state.vcap, state.ecap
    B = ops.kind.shape[0]
    idxs = jnp.arange(B, dtype=jnp.int32)

    ok_out = jnp.zeros((B,), jnp.bool_)
    val_out = jnp.full((B,), INF, jnp.float32)

    # ---------------- Phase 1: vertex ops -------------------------------
    isv = (ops.kind == PUTV) | (ops.kind == REMV)
    vkey = jnp.where(isv & (ops.u >= 0) & (ops.u < vcap), ops.u, NOKEY)
    perm = jnp.lexsort((idxs, vkey))
    sk, skind = vkey[perm], ops.kind[perm]
    first = sk != _prev(sk, jnp.int32(-1))
    pre_alive = state.alive[jnp.clip(sk, 0, vcap - 1)] & (sk != NOKEY)
    prev_is_put = _prev(skind, jnp.int32(NOP)) == PUTV
    present_before = jnp.where(first, pre_alive, prev_is_put)
    okv = jnp.where(skind == PUTV, ~present_before, present_before) & (sk != NOKEY)
    ok_out = jnp.where(isv, jnp.zeros((B,), jnp.bool_).at[perm].set(okv), ok_out)

    nxt = jnp.roll(sk, -1).at[B - 1].set(-1)
    is_last = sk != nxt
    scat_idx = jnp.where(is_last & (sk != NOKEY), sk, vcap)
    alive2 = state.alive.at[scat_idx].set(skind == PUTV, mode="drop")

    # Vertices successfully removed at any point in the batch: their incident
    # edges are invalidated (fresh empty edge-list on re-add, as in the
    # paper).  A slot dies when an endpoint matches one of the at most B
    # removed ids: compares fused into one pass over the table.
    removed = jnp.where(okv & (skind == REMV), sk, NOKEY)
    kill = (state.esrc != NOKEY) & (state.ew < INF) & (
        _is_any(state.esrc, removed) | _is_any(state.edst, removed))
    ew2 = jnp.where(kill, INF, state.ew)
    n_kill = jnp.sum(kill, dtype=jnp.int32)
    ecnt2 = _bump_killed_sources(state.ecnt, state.esrc, kill, n_kill)

    # ---------------- Phase 2: edge ops ---------------------------------
    ise = (ops.kind == PUTE) | (ops.kind == REME)
    in_range = (ops.u >= 0) & (ops.u < vcap) & (ops.v >= 0) & (ops.v < vcap)
    valid = ise & in_range & alive2[jnp.clip(ops.u, 0, vcap - 1)] \
        & alive2[jnp.clip(ops.v, 0, vcap - 1)]
    ku = jnp.where(valid, ops.u, NOKEY)
    kv = jnp.where(valid, ops.v, NOKEY)
    perm_e = jnp.lexsort((idxs, kv, ku))
    su, sv = ku[perm_e], kv[perm_e]
    skind_e, sw = ops.kind[perm_e], ops.w[perm_e]

    first_e = (su != _prev(su, jnp.int32(-1))) | (sv != _prev(sv, jnp.int32(-1)))
    slot = pair_searchsorted(state.esrc, state.edst, su, sv)
    slotc = jnp.clip(slot, 0, ecap - 1)
    key_present = (state.esrc[slotc] == su) & (state.edst[slotc] == sv) & (su != NOKEY)
    pre_live = key_present & (ew2[slotc] < INF)
    pre_w = jnp.where(pre_live, ew2[slotc], INF)

    prev_put = _prev(skind_e, jnp.int32(NOP)) == PUTE
    prev_w = _prev(sw, INF)
    pres_before = jnp.where(first_e, pre_live, prev_put)
    w_before = jnp.where(first_e, pre_w, jnp.where(prev_put, prev_w, INF))

    is_pute = skind_e == PUTE
    # Invalid ops (NOKEY-keyed) must not chain presence to one another.
    pres_before = pres_before & (su != NOKEY)
    ok_e = (su != NOKEY) & jnp.where(
        is_pute, ~pres_before | (w_before != sw), pres_before
    )
    ret_e = jnp.where(pres_before, w_before, INF)
    ok_out = jnp.where(ise, jnp.zeros((B,), jnp.bool_).at[perm_e].set(ok_e), ok_out)
    val_out = jnp.where(ise, jnp.full((B,), INF).at[perm_e].set(ret_e), val_out)

    # ecnt: one bump per successful out-edge-list mutation at the source.
    ecnt3 = ecnt2.at[jnp.where(ok_e, su, vcap)].add(1, mode="drop")

    # Final state per key = last op of each segment.
    nxt_u = jnp.roll(su, -1).at[B - 1].set(-1)
    nxt_v = jnp.roll(sv, -1).at[B - 1].set(-1)
    is_last_e = (su != nxt_u) | (sv != nxt_v)
    last_mask = is_last_e & (su != NOKEY)
    final_put = is_pute

    # In-place finals (key already occupies a slot, live or tombstoned).
    inplace = last_mask & key_present
    ew3 = ew2.at[jnp.where(inplace, slot, ecap)].set(
        jnp.where(final_put, sw, INF), mode="drop"
    )

    # Appends: final PutE on a key with no slot.  ``su`` is sorted, so the
    # compressed append list stays sorted.
    app = last_mask & final_put & ~key_present
    app_rank = jnp.cumsum(app.astype(jnp.int32)) - 1
    comp_idx = jnp.where(app, app_rank, B)
    cu = jnp.full((B,), NOKEY, jnp.int32).at[comp_idx].set(su, mode="drop")
    cv = jnp.full((B,), NOKEY, jnp.int32).at[comp_idx].set(sv, mode="drop")
    cw = jnp.full((B,), INF, jnp.float32).at[comp_idx].set(sw, mode="drop")
    n_app = jnp.sum(app.astype(jnp.int32))
    overflow = used_slots(state) + n_app > ecap

    # Shift merge: old slot j moves right by the number of appends that sort
    # before it, a count of B compares per slot; the appends then fill the
    # holes at their sorted slots ``pos + rank``.
    pos = jnp.where(cu != NOKEY,
                    pair_searchsorted(state.esrc, state.edst, cu, cv), ecap)
    esrc3, edst3, ew4 = shift_merge.spread([state.esrc, state.edst, ew3], pos)
    dest_new = jnp.where(cu != NOKEY, pos + idxs, ecap)
    esrc3 = esrc3.at[dest_new].set(cu, mode="drop")
    edst3 = edst3.at[dest_new].set(cv, mode="drop")
    ew4 = ew4.at[dest_new].set(cw, mode="drop")

    new_state = GraphState(
        alive=alive2, ecnt=ecnt3, esrc=esrc3, edst=edst3, ew=ew4,
        version=state.version + 1,
    )

    # ---------------- Phase 3: reads (GETV / GETE) ----------------------
    isgv = ops.kind == GETV
    isge = ops.kind == GETE
    gv_ok = alive2[jnp.clip(ops.u, 0, vcap - 1)] & in_range
    _, _, ge_live = find_edge_slots(new_state, jnp.where(isge, ops.u, NOKEY),
                                    jnp.where(isge, ops.v, NOKEY))
    ge_slot = pair_searchsorted(esrc3, edst3, ops.u, ops.v)
    ge_w = jnp.where(ge_live, ew4[jnp.clip(ge_slot, 0, ecap - 1)], INF)
    ok_out = jnp.where(isgv, gv_ok, ok_out)
    ok_out = jnp.where(isge, ge_live, ok_out)
    val_out = jnp.where(isge, ge_w, val_out)

    return new_state, OpResults(ok_out, val_out), overflow, n_kill


# Device traces name a program after its function: the commit that
# ``apply_ops`` launches keeps the name ``jit_apply_batch``.
_apply_batch.__name__ = _apply_batch.__qualname__ = "apply_batch"
_apply_batch_counted = jax.jit(_apply_batch)


@jax.jit
def apply_batch(state: GraphState, ops: OpBatch):
    """Apply one op batch. Returns ``(new_state, OpResults, overflow)``.

    ``overflow`` is True when appended edges did not fit in the slack; the
    caller must ``compact``/``grow_edges`` and retry (see ``apply_ops``).
    The input state is never corrupted on overflow (pure function).

    No step scatters or gathers over the edge table's ``ecap`` slots.  The
    merge moves each slot right by the number of appends sorted before it
    in one streaming pass (``kernels/shift_merge.py``), then writes the B
    appends into the holes.  RemV's invalidation compares each slot with
    the removed ids and bumps the killed edges' sources a chunk at a time
    (``_bump_killed_sources``).  Scatters and gathers touch at most ``B``
    or ``_KILL_CHUNK`` elements.
    """
    return _apply_batch_counted(state, ops)[:3]


class Invalidations:
    """Edges killed by the RemVs of the commits ``apply_ops`` made inside
    :func:`count_invalidations`."""

    __slots__ = ("n",)

    def __init__(self):
        self.n = 0


_INVALIDATIONS: contextvars.ContextVar = contextvars.ContextVar(
    "repro_invalidations", default=None)


@contextlib.contextmanager
def count_invalidations():
    """Tally the edges that ``apply_ops`` calls in this block (on this
    thread) invalidate; yields the :class:`Invalidations` it adds to."""
    tally = Invalidations()
    token = _INVALIDATIONS.set(tally)
    try:
        yield tally
    finally:
        _INVALIDATIONS.reset(token)


def apply_ops(state: GraphState, ops: Sequence[Tuple], batch_size: int | None = None):
    """Host convenience: apply ops with automatic compact/grow on overflow.

    Each retry applies the batch at most once: on overflow we ``compact``,
    and — when even a tombstone-free table cannot hold the worst case of one
    append per batch slot — ``grow_edges`` before the single retry.  The
    worst-case bound (``used + B <= ecap``) guarantees the retry cannot
    overflow again, at the cost of occasionally growing a table that a
    tighter count would have squeezed the batch into.  The committed
    batch's killed-edge count goes to the innermost ``count_invalidations``
    block, read in the same transfer as ``overflow``.
    """
    batch = make_batch(ops, batch_size)
    B = int(batch.kind.shape[0])
    while True:
        new_state, res, overflow, killed = _apply_batch_counted(state, batch)
        overflow, killed = jax.device_get((overflow, killed))
        if not overflow:
            tally = _INVALIDATIONS.get()
            if tally is not None:
                tally.n += int(killed)
            return new_state, res
        state = compact(state)
        while int(used_slots(state)) + B > state.ecap:
            state = grow_edges(state)


# ------------------------- dirty-set helpers ----------------------------
# The engine's version ring (``repro.engine``) derives per-commit
# *dirty-vertex sets* from these: the set of vertices whose out-edge list or
# liveness may differ between two committed snapshots.  ``ecnt[u]`` is bumped
# on every successful mutation of u's out-edges (including RemV-driven
# incident-edge invalidation, which bumps the *source* of every killed edge),
# so the ecnt delta alone covers every edge change; the alive delta covers
# vertex insertion/removal.  This is the paper's SNode/ecnt selectivity made
# into a first-class index.

@jax.jit
def dirty_vertices(prev: GraphState, new: GraphState) -> jax.Array:
    """bool[vcap]: vertices whose edge list or liveness changed prev -> new.

    Both states must share ``vcap`` (use ``dirty_vertices_padded`` across a
    ``grow_vertices`` boundary).
    """
    return (prev.ecnt != new.ecnt) | (prev.alive != new.alive)


def dirty_vertices_padded(prev: GraphState, new: GraphState) -> jax.Array:
    """``dirty_vertices`` tolerant of vertex-table growth between commits.

    Vertices that exist only in ``new`` are dirty iff alive or touched
    (their prev-side ecnt/alive are taken as zero/False).
    """
    if prev.vcap == new.vcap:
        return dirty_vertices(prev, new)
    if prev.vcap > new.vcap:
        raise ValueError("vertex table shrank between commits")
    pad = new.vcap - prev.vcap
    grown = prev._replace(
        alive=jnp.concatenate([prev.alive, jnp.zeros((pad,), jnp.bool_)]),
        ecnt=jnp.concatenate([prev.ecnt, jnp.zeros((pad,), jnp.int32)]),
    )
    return dirty_vertices(grown, new)


# ------------------------- standalone reads -----------------------------

@jax.jit
def get_v(state: GraphState, u) -> jax.Array:
    u = jnp.asarray(u, jnp.int32)
    return state.alive[jnp.clip(u, 0, state.vcap - 1)] & (u >= 0) & (u < state.vcap)


@jax.jit
def get_e(state: GraphState, u, v):
    u = jnp.asarray(u, jnp.int32)
    v = jnp.asarray(v, jnp.int32)
    idx, _, live = find_edge_slots(state, u, v)
    return live, jnp.where(live, state.ew[idx], INF)
