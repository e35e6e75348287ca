"""The commit's earlier formulation, kept for the differential tests only.

``apply_batch_reference`` merges a batch into the sorted edge table the way
``core/updates.apply_batch`` did before its scatter-free rewrite: per-slot
scatters over the whole table (``.at[dest_old].set``), a per-slot
``searchsorted`` for the shift, and a vertex-table gather per slot for
RemV invalidation.  It is slow on a TPU and exact everywhere, so the new
commit must equal it field for field.
"""
import jax
import jax.numpy as jnp

from repro.core.graph_state import (
    INF, NOKEY, GraphState, find_edge_slots, pair_searchsorted, used_slots,
)
from repro.core.updates import (
    GETE, GETV, NOP, PUTE, PUTV, REME, REMV, OpBatch, OpResults,
)


def _prev(arr, fill):
    rolled = jnp.roll(arr, 1)
    return rolled.at[0].set(fill)


@jax.jit
def apply_batch_reference(state: GraphState, ops: OpBatch):
    """Apply one op batch. Returns ``(new_state, OpResults, overflow)``.

    ``overflow`` is True when appended edges did not fit in the slack; the
    caller must ``compact``/``grow_edges`` and retry (see ``apply_ops``).
    The input state is never corrupted on overflow (pure function).
    """
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

    # vertices successfully removed at any point in the batch: their incident
    # edges are invalidated (fresh empty edge-list on re-add, as in the paper).
    remv_succ = okv & (skind == REMV)
    had_remv = jnp.zeros((vcap,), jnp.bool_).at[
        jnp.where(remv_succ, sk, vcap)
    ].max(jnp.ones((B,), jnp.bool_), mode="drop")

    esrcc = jnp.clip(state.esrc, 0, vcap - 1)
    edstc = jnp.clip(state.edst, 0, vcap - 1)
    kill = (state.esrc != NOKEY) & (state.ew < INF) & (
        had_remv[esrcc] | had_remv[edstc]
    )
    ew2 = jnp.where(kill, INF, state.ew)
    ecnt2 = state.ecnt.at[jnp.where(kill, state.esrc, vcap)].add(1, mode="drop")

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

    # Merge-scatter: shift old entries right past their insertion points.
    pos = pair_searchsorted(state.esrc, state.edst, cu, cv)
    shift_old = jnp.searchsorted(pos, jnp.arange(ecap, dtype=jnp.int32),
                                 side="right").astype(jnp.int32)
    dest_old = jnp.arange(ecap, dtype=jnp.int32) + shift_old
    esrc3 = jnp.full((ecap,), NOKEY, jnp.int32).at[dest_old].set(state.esrc, mode="drop")
    edst3 = jnp.full((ecap,), NOKEY, jnp.int32).at[dest_old].set(state.edst, mode="drop")
    ew4 = jnp.full((ecap,), INF, jnp.float32).at[dest_old].set(ew3, mode="drop")
    dest_new = jnp.where(cu != NOKEY, pos + jnp.arange(B, dtype=jnp.int32), ecap)
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

    return new_state, OpResults(ok_out, val_out), overflow
