"""Compatible-query batching: N pinned queries, one compiled dispatch.

The dispatcher groups admitted requests by ``(kind, version)`` and this
module turns each group into at most two compiled calls:

  * the **full** rung maps the single-source query (``queries.bfs`` /
    ``sssp`` / ``bc_dependencies``) over the stacked source axis — N
    concurrent BFS queries at version ``v`` cost one compiled program
    instead of N dispatches;
  * the **delta** rung maps the engine's delta kernels (``delta_bfs`` /
    ``delta_sssp`` / ``_delta_bc_at_cut``) over stacked
    ``(prior, dirty, src)`` lanes — each lane carries its own prior and
    its own accumulated dirty mask, so requests cached at *different*
    earlier versions still share the dispatch.

The lanes run one after another inside the program (``lax.map``), not
side by side (``jax.vmap``): the vmapped gathers over the ``ecap``-long
edge table put the lane axis minor, and a TPU tile pads a minor axis of
4 lanes to 128 — at Graph500 scale 20 the vmapped BFS rung needed
16.8 GB of a 15.75 GB chip, the mapped one 0.7 GB.  Per-lane answers are
bit-identical to the sequential single-source calls, since each lane
runs exactly that call.  The concurrent differential suite
(`tests/stream_differential.py`) holds this as its oracle.

Classification (which rung a request rides) reuses the ladder's own
pieces — ``ring.dirty_between``, ``_dirty_stats``, the per-kind
threshold consult, ``bc_level_cut`` — so the batched ladder demotes on
exactly the same evidence as ``engine.incremental``'s sequential one.

Lane stacks are padded up to the next power of two (replicating lane 0,
whose extra output rows are discarded) so the number of compiled batch
variants stays logarithmic in ``max_batch`` instead of linear; each
padding lane runs lane 0's query once more.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import queries
from repro.engine.incremental import _delta_bc_at_cut, _dirty_stats, \
    delta_bfs, delta_sssp

__all__ = ["Lane", "classify_local", "dispatch_local_group", "pad_pow2"]


def _lanes(query):
    """``query(state, *lane_args)`` mapped over stacked lanes in one
    compiled program; the state is shared by every lane."""
    return jax.jit(lambda state, *lanes: lax.map(
        lambda lane: query(state, *lane), lanes))


#: full rungs: state shared, source axis stacked.
_VFULL = {"bfs": _lanes(queries.bfs), "sssp": _lanes(queries.sssp),
          "bc": _lanes(queries.bc_dependencies)}

#: delta rungs: state shared; prior / dirty-or-cut / source stacked per
#: lane.
_VDELTA = {"bfs": _lanes(delta_bfs), "sssp": _lanes(delta_sssp),
           "bc": _lanes(_delta_bc_at_cut)}

#: reached-region mask of a cached local result, per kind (the unchanged
#: test: dirty ∩ reached == ∅ ⇒ the cached answer stands).
_REACHED = {
    "bfs": lambda r: r.reached,
    "sssp": lambda r: r.dist < jnp.inf,
    "bc": lambda r: r.level >= 0,
}


def pad_pow2(n: int) -> int:
    """Smallest power of two >= n (compile-variant bucketing)."""
    size = 1
    while size < n:
        size *= 2
    return size


@dataclass
class Lane:
    """One request's slice of a batched dispatch."""

    index: int              # position in the dispatcher's group
    src: int
    mode: str               # "unchanged" | "delta" | "full"
    prior: object = None    # cached result (unchanged/delta lanes)
    dirty: object = None    # accumulated dirty mask (delta bfs/sssp)
    cut: object = None      # warm-start level cut (delta bc)
    dirty_frac: Optional[float] = None


def classify_local(service, kind: str, src: int, version: int,
                   state) -> Lane:
    """Which rung does this request ride?  Mirrors the gates of
    ``engine.incremental.incremental_*`` exactly (prior usability, the
    unchanged shortcut, the threshold consult, BC's level-cut floor), so
    a batched query demotes on the same evidence as a sequential one.
    """
    with service._cache_lock:
        slot = service._cache.get((kind, src))
    if slot is None or not service._breaker_allows(kind):
        return Lane(0, src, "full")
    prior = slot.result
    usable = bool(prior.ok) and (
        prior.level.shape[0] == state.vcap if kind == "bc"
        else prior.dist.shape[0] == state.vcap)
    if not usable:
        return Lane(0, src, "full")
    if slot.version == version:
        return Lane(0, src, "unchanged", prior=prior)
    dirty = service.ring.dirty_between(slot.version, version)
    if dirty is None:
        return Lane(0, src, "full")
    reached = _REACHED[kind](prior)
    n_dirty, touched = (int(x) for x in _dirty_stats(reached, dirty))
    frac = n_dirty / state.vcap
    if not touched:
        return Lane(0, src, "unchanged", prior=prior, dirty_frac=frac)
    if frac > service._threshold(kind):
        return Lane(0, src, "full", dirty_frac=frac)
    if kind == "bc":
        cut = queries.bc_level_cut(prior.level, dirty, state.alive)
        if int(cut) < 1:
            return Lane(0, src, "full", dirty_frac=frac)
        return Lane(0, src, "delta", prior=prior, cut=cut, dirty_frac=frac)
    return Lane(0, src, "delta", prior=prior, dirty=dirty, dirty_frac=frac)


def _stack_pad(trees: List, pad: int):
    """Stack pytrees along a new leading lane axis, replicating lane 0
    ``pad`` more times (padding lanes are discarded by the caller)."""
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)
    if pad:
        stacked = jax.tree_util.tree_map(
            lambda x: jnp.concatenate(
                [x, jnp.repeat(x[:1], pad, axis=0)], axis=0), stacked)
    return stacked


def _unstack(batched, n: int) -> List:
    """Lane ``i``'s result tree, for the first ``n`` (unpadded) lanes."""
    return [jax.tree_util.tree_map(lambda x: x[i], batched)
            for i in range(n)]


def dispatch_local_group(service, kind: str, state,
                         lanes: List[Lane]) -> Tuple[List, Dict[str, int]]:
    """Run one ``(kind, version)`` group's device work.

    Returns ``(results, dispatch_sizes)`` where ``results[i]`` answers
    ``lanes[i]`` and ``dispatch_sizes`` maps rung name -> lane count for
    each compiled call that actually ran.  Lanes may be *reclassified*
    ``delta -> full`` on the way (a delta SSSP that surfaced a negative
    cycle re-runs full for the canonical answer, exactly the
    ``incremental_sssp`` contract) — callers must read ``lane.mode``
    after this returns.
    """
    results: List = [None] * len(lanes)
    sizes: Dict[str, int] = {}
    full_lanes = [ln for ln in lanes if ln.mode == "full"]
    delta_lanes = [ln for ln in lanes if ln.mode == "delta"]
    for ln in lanes:
        if ln.mode == "unchanged":
            results[ln.index] = ln.prior

    if delta_lanes:
        n = len(delta_lanes)
        pad = pad_pow2(n) - n
        srcs = jnp.asarray([ln.src for ln in delta_lanes], jnp.int32)
        if pad:
            srcs = jnp.concatenate([srcs, jnp.repeat(srcs[:1], pad)])
        priors = _stack_pad([ln.prior for ln in delta_lanes], pad)
        if kind == "bc":
            cuts = jnp.asarray([ln.cut for ln in delta_lanes], jnp.int32)
            if pad:
                cuts = jnp.concatenate([cuts, jnp.repeat(cuts[:1], pad)])
            out = _VDELTA[kind](state, priors, cuts, srcs)
        else:
            dirt = _stack_pad([ln.dirty for ln in delta_lanes], pad)
            out = _VDELTA[kind](state, priors, dirt, srcs)
        per_lane = _unstack(out, n)
        sizes["delta"] = n
        for ln, res in zip(delta_lanes, per_lane):
            if kind == "sssp" and bool(res.negcycle):
                # Born-since-prior negative cycle: the full query's
                # partially-relaxed distances are the canonical answer.
                ln.mode = "full"
                full_lanes.append(ln)
            else:
                results[ln.index] = res

    if full_lanes:
        n = len(full_lanes)
        pad = pad_pow2(n) - n
        srcs = jnp.asarray([ln.src for ln in full_lanes], jnp.int32)
        if pad:
            srcs = jnp.concatenate([srcs, jnp.repeat(srcs[:1], pad)])
        out = _VFULL[kind](state, srcs)
        per_lane = _unstack(out, n)
        sizes["full"] = n
        for ln, res in zip(full_lanes, per_lane):
            results[ln.index] = res

    return results, sizes
