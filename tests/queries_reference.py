"""The COO queries' earlier formulation, kept for the differential tests only.

These are ``core/queries.py``'s and ``engine/incremental.py``'s BFS, SSSP,
Brandes and tree-parent programs as they were before the segmented
rewrite: every level or relax pass scatters over all ``ecap`` edge slots
(``.at[dstc].max/min/add`` forward, ``.at[srcc].add`` backward).  They are
slow on a TPU and exact everywhere, so the segmented forms must equal them
bit for bit (BC ``delta`` to float32 summation order).
"""
import jax
import jax.numpy as jnp
from jax import lax

from repro.core.graph_state import INF, NOKEY, GraphState, live_edge_mask
from repro.core.queries import BCResult, BFSResult, SSSPResult
from repro.engine.incremental import _poison


def _edge_views(state: GraphState):
    live = live_edge_mask(state)
    srcc = jnp.where(live, state.esrc, 0)
    dstc = jnp.where(live, state.edst, 0)
    return live, srcc, dstc


@jax.jit
def bfs_reference(state: GraphState, src) -> BFSResult:
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    live, srcc, dstc = _edge_views(state)
    ok = state.alive[jnp.clip(src, 0, vcap - 1)] & (src >= 0) & (src < vcap)

    reached0 = jnp.zeros((vcap,), jnp.bool_).at[src].set(ok, mode="drop")
    dist0 = jnp.where(reached0, 0, -1).astype(jnp.int32)
    parent0 = jnp.full((vcap,), NOKEY, jnp.int32)

    def cond(carry):
        _, _, _, frontier, lvl = carry
        return frontier.any() & (lvl < vcap)

    def body(carry):
        reached, dist, parent, frontier, lvl = carry
        act = live & frontier[srcc]
        hit = jnp.zeros((vcap,), jnp.bool_).at[dstc].max(act, mode="drop")
        newly = hit & ~reached
        cand_par = jnp.full((vcap,), NOKEY, jnp.int32).at[dstc].min(
            jnp.where(act, srcc, NOKEY), mode="drop")
        parent = jnp.where(newly, cand_par, parent)
        dist = jnp.where(newly, lvl + 1, dist)
        return reached | newly, dist, parent, newly, lvl + 1

    reached, dist, parent, _, _ = lax.while_loop(
        cond, body, (reached0, dist0, parent0, reached0, jnp.int32(0)))
    return BFSResult(ok, reached, dist, parent)


def _relax_once(dist, live, srcc, dstc, ew, vcap):
    cand = jnp.full((vcap,), INF).at[dstc].min(
        jnp.where(live, dist[srcc] + ew, INF), mode="drop")
    return jnp.minimum(dist, cand)


def _relax_fixpoint(dist0, live, srcc, dstc, ew, vcap):
    def cond(carry):
        _, changed, it = carry
        return changed & (it < vcap)

    def body(carry):
        dist, _, it = carry
        nd = _relax_once(dist, live, srcc, dstc, ew, vcap)
        return nd, (nd < dist).any(), it + 1

    return lax.while_loop(cond, body, (dist0, jnp.bool_(True), jnp.int32(0)))


@jax.jit
def sssp_reference(state: GraphState, src) -> SSSPResult:
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    live, srcc, dstc = _edge_views(state)
    ew = jnp.where(live, state.ew, INF)
    ok_src = state.alive[jnp.clip(src, 0, vcap - 1)] & (src >= 0) & (src < vcap)

    dist0 = jnp.full((vcap,), INF).at[src].set(
        jnp.where(ok_src, 0.0, INF), mode="drop")
    dist, changed, _ = _relax_fixpoint(dist0, live, srcc, dstc, ew, vcap)
    negcycle = changed
    tight = live & (dist[dstc] == dist[srcc] + ew) & (dist[srcc] < INF)
    parent = jnp.full((vcap,), NOKEY, jnp.int32).at[dstc].min(
        jnp.where(tight, srcc, NOKEY), mode="drop")
    parent = parent.at[jnp.clip(src, 0, vcap - 1)].set(NOKEY)
    return SSSPResult(ok_src & ~negcycle, negcycle, dist, parent)


def _bc_coo_sweep(live, srcc, dstc, vcap, level0, sigma0, front0, lvl0):
    def fcond(carry):
        _, _, frontier, lvl = carry
        return frontier.any() & (lvl < vcap)

    def fbody(carry):
        level, sigma, frontier, lvl = carry
        act = live & frontier[srcc]
        hit = jnp.zeros((vcap,), jnp.bool_).at[dstc].max(act, mode="drop")
        newly = hit & (level < 0)
        adds = jnp.zeros((vcap,), jnp.float32).at[dstc].add(
            jnp.where(act, sigma[srcc], 0.0), mode="drop")
        sigma = jnp.where(newly, adds, sigma)
        level = jnp.where(newly, lvl + 1, level)
        return level, sigma, newly, lvl + 1

    level, sigma, _, _ = lax.while_loop(
        fcond, fbody, (level0, sigma0, front0, jnp.asarray(lvl0, jnp.int32)))

    sig_src = sigma[srcc]
    sig_dst = jnp.where(sigma[dstc] > 0, sigma[dstc], 1.0)

    def bcond(carry):
        _, l = carry
        return l >= 0

    def bbody(carry):
        delta, l = carry
        on_lvl = live & (level[srcc] == l) & (level[dstc] == l + 1)
        contrib = jnp.where(on_lvl, sig_src / sig_dst * (1.0 + delta[dstc]),
                            0.0)
        delta = delta + jnp.zeros((vcap,), jnp.float32).at[srcc].add(
            contrib, mode="drop")
        return delta, l - 1

    delta, _ = lax.while_loop(
        bcond, bbody, (jnp.zeros((vcap,), jnp.float32), jnp.max(level)))
    delta = jnp.where(level == 0, 0.0, delta)
    return level, sigma, delta


@jax.jit
def bc_dependencies_reference(state: GraphState, src) -> BCResult:
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    live, srcc, dstc = _edge_views(state)
    ok = state.alive[jnp.clip(src, 0, vcap - 1)] & (src >= 0) & (src < vcap)

    level0 = jnp.full((vcap,), -1, jnp.int32).at[src].set(
        jnp.where(ok, 0, -1), mode="drop")
    sigma0 = jnp.zeros((vcap,), jnp.float32).at[src].set(
        jnp.where(ok, 1.0, 0.0), mode="drop")
    front0 = level0 == 0
    level, sigma, delta = _bc_coo_sweep(
        live, srcc, dstc, vcap, level0, sigma0, front0, jnp.int32(0))
    return BCResult(ok, delta, sigma, level)


@jax.jit
def bfs_tree_parents_reference(state: GraphState, dist, srcs):
    vcap = state.vcap
    live, srcc, dstc = _edge_views(state)

    def one(d, s):
        distf = jnp.where(d >= 0, d.astype(jnp.float32), INF)
        tree = live & (distf[srcc] + 1.0 == distf[dstc]) & (distf[srcc] < INF)
        parent = jnp.full((vcap,), NOKEY, jnp.int32).at[dstc].min(
            jnp.where(tree, srcc, NOKEY), mode="drop")
        parent = jnp.where(d >= 0, parent, NOKEY)
        return parent.at[jnp.clip(s, 0, vcap - 1)].set(NOKEY)

    return jax.vmap(one)(dist, srcs)


@jax.jit
def sssp_tree_parents_reference(state: GraphState, dist, srcs):
    vcap = state.vcap
    live, srcc, dstc = _edge_views(state)
    ew = jnp.where(live, state.ew, INF)

    def one(d, s):
        tight = live & (d[dstc] == d[srcc] + ew) & (d[srcc] < INF)
        parent = jnp.full((vcap,), NOKEY, jnp.int32).at[dstc].min(
            jnp.where(tight, srcc, NOKEY), mode="drop")
        return parent.at[jnp.clip(s, 0, vcap - 1)].set(NOKEY)

    return jax.vmap(one)(dist, srcs)


@jax.jit
def delta_bfs_reference(state: GraphState, prior: BFSResult, dirty,
                        src) -> BFSResult:
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    live, srcc, dstc = _edge_views(state)
    ok = state.alive[jnp.clip(src, 0, vcap - 1)] & (src >= 0) & (src < vcap)

    priorf = prior.dist.astype(jnp.float32)
    poison = _poison(state, prior.parent, prior.reached, priorf, dirty,
                     check_weight=False)
    keep = prior.reached & ~poison
    dist0 = jnp.where(keep, priorf, INF)
    dist0 = dist0.at[src].set(jnp.where(ok, 0.0, INF), mode="drop")
    unit = jnp.ones((state.ecap,), jnp.float32)
    distf, _, _ = _relax_fixpoint(dist0, live, srcc, dstc, unit, vcap)
    reached = distf < INF
    dist = jnp.where(reached, distf, -1.0).astype(jnp.int32)
    parent = bfs_tree_parents_reference(state, dist[None], src[None])[0]
    return BFSResult(ok, reached, dist, parent)


@jax.jit
def delta_sssp_reference(state: GraphState, prior: SSSPResult, dirty,
                         src) -> SSSPResult:
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    live, srcc, dstc = _edge_views(state)
    ew = jnp.where(live, state.ew, INF)
    ok_src = state.alive[jnp.clip(src, 0, vcap - 1)] & (src >= 0) & (src < vcap)

    prior_reached = prior.dist < INF
    poison = _poison(state, prior.parent, prior_reached, prior.dist, dirty,
                     check_weight=True)
    keep = prior_reached & ~poison
    dist0 = jnp.where(keep, prior.dist, INF)
    dist0 = dist0.at[src].set(jnp.where(ok_src, 0.0, INF), mode="drop")
    dist, changed, _ = _relax_fixpoint(dist0, live, srcc, dstc, ew, vcap)
    negcycle = changed
    parent = sssp_tree_parents_reference(state, dist[None], src[None])[0]
    return SSSPResult(ok_src & ~negcycle, negcycle, dist, parent)


@jax.jit
def delta_bc_reference(state: GraphState, prior: BCResult, cut,
                       src) -> BCResult:
    src = jnp.asarray(src, jnp.int32)
    vcap = state.vcap
    live, srcc, dstc = _edge_views(state)
    ok = state.alive[jnp.clip(src, 0, vcap - 1)] & (src >= 0) & (src < vcap)

    cut = jnp.asarray(cut, jnp.int32)
    keep = (prior.level >= 0) & (prior.level < cut)
    level0 = jnp.where(keep, prior.level, -1)
    sigma0 = jnp.where(keep, prior.sigma, 0.0)
    front0 = level0 == cut - 1
    level, sigma, delta = _bc_coo_sweep(
        live, srcc, dstc, vcap, level0, sigma0, front0, cut - 1)
    return BCResult(ok, delta, sigma, level)
