"""Sharded tile-grid engine benchmark: multi-device queries + view upkeep.

Runs on host-platform placeholder devices (``--devices``, default 4 — set
BEFORE jax imports, like ``launch/dryrun.py``), so the numbers measure the
sharded *program structure* (collective volume, tile-skip rates, refresh
locality) rather than real accelerator parallelism: all shards share one
CPU, so ``speedup_sharded_vs_local`` is an overhead ratio here and a
scaling ratio only on a real mesh.  What it reports:

  * **view upkeep** — ``build_sharded_view`` from scratch vs
    ``refresh_sharded_view`` re-deriving only the dirty tile rows of a
    localized commit (the headline: refresh must beat rebuild at n=2048);
  * **queries** — distributed bfs/sssp/bc wall time vs the single-device
    ``core.queries`` batched path on the same snapshot, with results
    cross-checked (dist/level/sigma bit-identical, delta/scores allclose);
  * **per-shard tile-skip hit rate** — what fraction of its band each
    shard's masked kernels elide;
  * **collective bytes per level** — measured from the compiled HLO
    (``obs.hlo.parse_collective_bytes`` on the while-loop body) next
    to the formula value S x Vp x (1B bfs | 4B sssp);
  * **ring vs gather BC** — the SUMMA band-rotation BC (``bc_mode="ring"``)
    against the all-gather oracle: wall time, per-device temp bytes off
    ``memory_analysis`` (gather materialises the O(Vp^2) grid, ring holds
    O(Vp^2/n)), and the measured ``collective-permute`` bytes next to the
    O(Vp^2/n)-per-rotation formula.

Prints the usual ``name,us_per_call,derived`` CSV rows and always writes
``BENCH_shard.json``.

    PYTHONPATH=src python benchmarks/bench_shard.py [--n 2048] \
        [--devices 4] [--sources 16] [--json BENCH_shard.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def _parse_args(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--n", type=int, default=2048,
                   help="live vertex count (power of two for R-MAT)")
    p.add_argument("--edge-factor", type=int, default=8)
    p.add_argument("--devices", type=int, default=4,
                   help="host-platform placeholder device count")
    p.add_argument("--sources", type=int, default=16,
                   help="bfs/sssp/bc source batch (multiple of --devices)")
    p.add_argument("--hot-frac", type=float, default=0.02,
                   help="fraction of vertices a refresh commit touches")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--bc-chunk", type=int, default=None)
    p.add_argument("--json", default="BENCH_shard.json")
    return p.parse_args(argv)


ARGS = _parse_args(sys.argv[1:])
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={ARGS.devices} "
    + os.environ.get("XLA_FLAGS", ""))

import time  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import PUTE, REME, apply_ops, queries  # noqa: E402
from repro.core.updates import dirty_vertices  # noqa: E402
from repro.data import load_rmat_graph  # noqa: E402
from repro.obs.hlo import parse_collective_bytes  # noqa: E402
from repro.shard import (  # noqa: E402
    ShardedGraphService,
    as_graph_mesh,
    bc_batched,
    bfs,
    build_sharded_view,
    delta_bc_sharded,
    delta_bfs_sharded,
    delta_sssp_sharded,
    query_fn,
    refresh_sharded_view,
    refresh_stats,
    sharded_occupancy_stats,
    sssp,
)

ROWS: list[dict] = []


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)
    ROWS.append({"name": name, "us_per_call": round(us, 1),
                 "derived": derived})


def _block(res):
    if hasattr(res, "w") and hasattr(res, "occ"):  # ShardedTileView
        res.w.block_until_ready()
        res.occ.block_until_ready()
        return res
    jax.tree.map(lambda x: x.block_until_ready(), res)
    return res


def _time(fn, *args, **kw):
    _block(fn(*args, **kw))  # warm compilation
    best = float("inf")
    # best-of-3: host-platform placeholder devices share one CPU, so
    # single-shot timings swing by tens of percent
    for _ in range(3):
        t0 = time.perf_counter()
        out = _block(fn(*args, **kw))
        best = min(best, time.perf_counter() - t0)
    return best, out


def hot_commit(rng, g, n, hot_frac):
    """One localized commit: edge churn confined to a contiguous hot range."""
    size = max(2, int(n * hot_frac))
    base = int(rng.integers(0, max(1, n - size)))
    ops = []
    for _ in range(size):
        u = base + int(rng.integers(0, size))
        v = int(rng.integers(0, n))
        if rng.random() < 0.3:
            ops.append((REME, u, v))
        else:
            ops.append((PUTE, u, v, float(rng.integers(1, 9))))
    g2, _ = apply_ops(g, ops)
    return g2


def bench_view(mesh, g, n, hot_frac, seed):
    rng = np.random.default_rng(seed)
    t_build, view = _time(build_sharded_view, g, mesh)
    occ = sharded_occupancy_stats(view)
    _row("shard_view_build", t_build * 1e6,
         f"vp={view.vp};shards={view.n_shards};"
         f"tile_skip_rate={occ['tile_skip_rate']:.4f}")

    g2 = hot_commit(rng, g, n, hot_frac)
    dirty = dirty_vertices(g, g2)
    n_rows = int(np.unique(np.flatnonzero(np.asarray(jax.device_get(dirty)))
                           // view.tile).size)
    # warm the row-refresh compile cache, then take best-of-3 (host-forced
    # placeholder devices share one CPU, so single-shot timings are noisy);
    # each refresh CONSUMES its input view (donated buffers), so a fresh
    # base view is built outside the timed region per repeat.
    refresh_sharded_view(g2, build_sharded_view(g, mesh), dirty)
    t_refresh = float("inf")
    rows0, disp0 = refresh_stats.rows, refresh_stats.dispatches
    for _ in range(3):
        base_view = _block(build_sharded_view(g, mesh))
        t0 = time.perf_counter()
        view2 = _block(refresh_sharded_view(g2, base_view, dirty))
        t_refresh = min(t_refresh, time.perf_counter() - t0)
    # dispatch accounting per refresh: pre-batching cost was one shard_map
    # launch per dirty row; same-width batching fuses them.
    n_refresh = 3
    rows_per = (refresh_stats.rows - rows0) // n_refresh
    disp_per = (refresh_stats.dispatches - disp0) // n_refresh
    t_rebuild = min(_time(build_sharded_view, g2, mesh)[0] for _ in range(3))
    speedup = t_rebuild / t_refresh
    _row("shard_view_refresh", t_refresh * 1e6,
         f"dirty_tile_rows={n_rows};vs_rebuild={speedup:.2f}x;"
         f"dispatches={disp_per}/{rows_per}rows")
    return view2, g2, {
        "t_build_s": round(t_build, 4),
        "t_refresh_s": round(t_refresh, 4),
        "t_rebuild_s": round(t_rebuild, 4),
        "dirty_tile_rows": n_rows,
        "refresh_vs_rebuild": round(speedup, 2),
        "dispatches_unbatched": rows_per,  # one per dirty row before batching
        "dispatches_batched": disp_per,
        "occupancy": occ,
    }


def _compiled(mesh, view, g, kind, srcs, src_chunk=None):
    fn = query_fn(mesh, kind, view.tile, False, src_chunk)
    return fn.lower(view.w, view.occ, g.alive, g.ecnt, srcs,
                    g.version).compile()


def _collective_bytes(mesh, view, g, kind, srcs, src_chunk=None):
    """Per-level collective bytes off the compiled HLO (the while body's
    all-reduce — and the ring's band permutes — appear once per loop in
    the program text)."""
    txt = _compiled(mesh, view, g, kind, srcs, src_chunk).as_text()
    return parse_collective_bytes(txt)


def _temp_bytes(mesh, view, g, kind, srcs, src_chunk=None):
    """Per-device temp (scratch) bytes of the compiled program — where the
    gather path's materialised Vp^2 grid vs the ring path's O(Vp^2/n) band
    shows up."""
    try:
        ma = _compiled(mesh, view, g, kind, srcs, src_chunk).memory_analysis()
        return int(ma.temp_size_in_bytes)
    except Exception:  # pragma: no cover - backend without memory stats
        return None


def bench_queries(mesh, view, g, n_sources, bc_chunk):
    srcs = jnp.arange(n_sources, dtype=jnp.int32)
    am, wd, alive = queries.dense_views(g)
    out = {}

    t_s, r = _time(bfs, view, g, srcs)
    t_l, ref = _time(queries.bfs_batched_dense, am, srcs, alive)
    assert np.array_equal(np.asarray(r.dist), np.asarray(ref)), "bfs drift"
    coll = _collective_bytes(mesh, view, g, "bfs", srcs)
    _row("shard_bfs", t_s * 1e6,
         f"local={t_l * 1e6:.1f}us;ratio={t_l / t_s:.2f}x;"
         f"coll_bytes_level={coll.get('all-reduce', 0)}")
    out["bfs"] = {"t_sharded_s": round(t_s, 4), "t_local_s": round(t_l, 4),
                  "speedup_sharded_vs_local": round(t_l / t_s, 2),
                  "collective_bytes_per_level": coll.get("all-reduce", 0),
                  "formula_bytes_per_level": n_sources * view.vp}

    t_s, r = _time(sssp, view, g, srcs)
    t_l, (dref, negref) = _time(queries.sssp_batched_dense, wd, srcs, alive)
    assert np.array_equal(np.asarray(r.dist), np.asarray(dref)), "sssp drift"
    assert np.array_equal(np.asarray(r.negcycle), np.asarray(negref))
    coll = _collective_bytes(mesh, view, g, "sssp", srcs)
    _row("shard_sssp", t_s * 1e6,
         f"local={t_l * 1e6:.1f}us;ratio={t_l / t_s:.2f}x;"
         f"coll_bytes_level={coll.get('all-reduce', 0)}")
    out["sssp"] = {"t_sharded_s": round(t_s, 4), "t_local_s": round(t_l, 4),
                   "speedup_sharded_vs_local": round(t_l / t_s, 2),
                   "collective_bytes_per_level": coll.get("all-reduce", 0),
                   "formula_bytes_per_level": 4 * n_sources * view.vp}

    t_s, r = _time(bc_batched, view, g, srcs, src_chunk=bc_chunk)
    t_l, (d, s, lv, ok) = _time(queries.bc_batched_dense, am, srcs, alive,
                                src_chunk=bc_chunk)
    assert np.array_equal(np.asarray(r.level), np.asarray(lv)), "bc drift"
    assert np.array_equal(np.asarray(r.sigma), np.asarray(s))
    assert np.allclose(np.asarray(r.delta), np.asarray(d),
                       rtol=1e-5, atol=1e-5)
    _row("shard_bc", t_s * 1e6,
         f"local={t_l * 1e6:.1f}us;ratio={t_l / t_s:.2f}x;"
         f"src_chunk={bc_chunk}")
    out["bc"] = {"t_sharded_s": round(t_s, 4), "t_local_s": round(t_l, 4),
                 "speedup_sharded_vs_local": round(t_l / t_s, 2),
                 "src_chunk": bc_chunk}

    # ---- ring-mode BC: SUMMA band rotation vs the gathered oracle -----
    # Crossover economics: gather pays O(Vp^2/n x (n-1)) all-gather bytes
    # ONCE per query plus O(Vp^2) per-shard memory; ring pays O(Vp^2/n)
    # permute bytes per rotation, (levels x n) rotations per sweep, but
    # holds per-shard memory at O(Vp^2/n).  On host-platform placeholder
    # devices the timing ratio is pure program overhead — the memory and
    # byte columns are the hardware-independent facts.
    t_r, rr = _time(bc_batched, view, g, srcs, src_chunk=bc_chunk,
                    bc_mode="ring")
    assert np.array_equal(np.asarray(rr.level), np.asarray(r.level)), \
        "ring level drift"
    assert np.array_equal(np.asarray(rr.sigma), np.asarray(r.sigma)), \
        "ring sigma drift"
    assert np.allclose(np.asarray(rr.scores), np.asarray(r.scores),
                       rtol=1e-4, atol=1e-4), "ring score drift"
    coll = _collective_bytes(mesh, view, g, "bc_ring", srcs,
                             src_chunk=bc_chunk)
    permute = coll.get("collective-permute", 0)
    n_dev = view.n_shards
    per_rot = (view.band * view.vp            # int8 {0,1} band
               + view.rows_per_shard * view.n_tiles * 4)  # O(Vp^2/n)
    mem_g = _temp_bytes(mesh, view, g, "bc", srcs, src_chunk=bc_chunk)
    mem_r = _temp_bytes(mesh, view, g, "bc_ring", srcs, src_chunk=bc_chunk)
    _row("shard_bc_ring", t_r * 1e6,
         f"gather={t_s * 1e6:.1f}us;ring_vs_gather={t_s / t_r:.2f}x;"
         f"permute_bytes={permute};per_rot_formula={per_rot};"
         f"temp_bytes={mem_r}vs{mem_g}")
    out["bc"]["ring"] = {
        "t_ring_s": round(t_r, 4),
        "ring_vs_gather": round(t_s / t_r, 2),
        "rotations_per_product": n_dev,
        "permute_bytes_hlo": permute,
        "permute_bytes_per_rotation_formula": per_rot,
        "temp_bytes_gather": mem_g,
        "temp_bytes_ring": mem_r,
        "temp_bytes_ratio": (round(mem_g / mem_r, 2)
                             if mem_g and mem_r else None),
    }
    return out


def dirty_commit(rng, g, n, frac):
    """One commit dirtying ~frac of the vertices (contiguous hot range)."""
    return hot_commit(rng, g, n, frac)


def _deep_hot_set(prior_dist, n, max_size):
    """The deepest vertices below the median level of EVERY source's tree.

    The delta cuts are per source, and the warm loops run to the max over
    sources — one shallow cut serializes the whole batch — so the
    deep-churn regime needs vertices that are deep (or unreached) from
    every source at once; among those, the deepest bind the cuts least,
    so they are taken deepest-first (by each vertex's shallowest reached
    level, the quantity ``bc_level_cut`` minimizes over).
    """
    lv = np.asarray(jax.device_get(prior_dist))
    depth = lv.max()
    big = np.iinfo(np.int32).max
    lvm = np.where(lv >= 0, lv, big).min(axis=0)  # binding level per vertex
    cand = np.flatnonzero((lvm > depth // 2) & (lvm < big))
    cand = cand[cand < n]
    return cand[np.argsort(-lvm[cand], kind="stable")][:max(2, max_size)]


def bench_incremental(mesh, view, g, n, n_sources, bc_chunk, seed,
                      fracs=(0.05, 0.2, 0.5)):
    """Sharded delta vs full recompute, and the crossover as dirt grows.

    Two regimes, both ≤ the smallest fraction of dirty vertices:

      * the **headline rows** (``shard_*_incr``) churn a hot set below the
        median level of every source's forward tree — the regime the level
        cut targets (deep churn ⇒ deep cuts ⇒ the warm loops skip the
        shallow passes; the SSSP poison keeps almost everything);
      * the **crossover table** places the hot range uniformly at random
        (the local engine benchmark's regime) and grows the dirty fraction
        (5%, 20%, 50%) — delta shrinks toward 1x as more of the graph
        moves, which is exactly why the service ladder has a threshold.

    Every delta result is cross-checked bit-identical to its full
    counterpart before being timed.
    """
    rng = np.random.default_rng(seed + 1)
    srcs = jnp.arange(n_sources, dtype=jnp.int32)
    prior_b = _block(bfs(view, g, srcs))
    prior_s = _block(sssp(view, g, srcs))
    prior_c = _block(bc_batched(view, g, srcs, src_chunk=bc_chunk))

    # ---- headline: deep churn at <= fracs[0] dirty --------------------
    deep = _deep_hot_set(prior_b.dist, n, int(n * fracs[0]) // 2)
    ops = [(PUTE, int(u), int(rng.integers(0, n)), float(rng.integers(1, 9)))
           for u in deep]
    g3, _ = apply_ops(g, ops)
    dirty3 = dirty_vertices(g, g3)
    frac3 = float(np.asarray(jax.device_get(dirty3)).mean())
    view3 = build_sharded_view(g3, mesh)
    out = {}
    for kind, delta_fn, full_fn, prior in (
            ("bfs", delta_bfs_sharded, bfs, prior_b),
            ("sssp", delta_sssp_sharded, sssp, prior_s)):
        t_d, d = _time(delta_fn, view3, g3, prior, dirty3, srcs)
        t_f, f = _time(full_fn, view3, g3, srcs)
        assert np.array_equal(np.asarray(d.dist), np.asarray(f.dist)), kind
        assert np.array_equal(np.asarray(d.parent), np.asarray(f.parent))
        _row(f"shard_{kind}_incr", t_d * 1e6,
             f"full_us={t_f * 1e6:.1f};speedup={t_f / t_d:.2f}x;"
             f"dirty_frac={frac3:.3f};deep_hot={deep.size}")
        out[kind] = {"t_delta_s": round(t_d, 4), "t_full_s": round(t_f, 4),
                     "speedup_delta_vs_full": round(t_f / t_d, 2),
                     "dirty_frac": round(frac3, 4)}
    t_dc, dc = _time(delta_bc_sharded, view3, g3, prior_c, dirty3, srcs,
                     src_chunk=bc_chunk)
    t_fc, fc = _time(bc_batched, view3, g3, srcs, src_chunk=bc_chunk)
    assert np.array_equal(np.asarray(dc.level), np.asarray(fc.level))
    assert np.array_equal(np.asarray(dc.sigma), np.asarray(fc.sigma))
    assert np.array_equal(np.asarray(dc.scores), np.asarray(fc.scores))
    _row("shard_bc_incr", t_dc * 1e6,
         f"full_us={t_fc * 1e6:.1f};speedup={t_fc / t_dc:.2f}x;"
         f"dirty_frac={frac3:.3f};deep_hot={deep.size}")
    out["bc"] = {"t_delta_s": round(t_dc, 4), "t_full_s": round(t_fc, 4),
                 "speedup_delta_vs_full": round(t_fc / t_dc, 2),
                 "dirty_frac": round(frac3, 4),
                 "deep_dirty_vertices": int(deep.size)}

    # Ring-mode delta BC: the prior's forward trees are mode-independent
    # (level/sigma bit-identical), so the gather prior warm-starts the
    # ring sweep directly; the cuts and per-source resume counters agree
    # by construction.
    t_dr, dr = _time(delta_bc_sharded, view3, g3, prior_c, dirty3, srcs,
                     src_chunk=bc_chunk, bc_mode="ring")
    t_fr, fr = _time(bc_batched, view3, g3, srcs, src_chunk=bc_chunk,
                     bc_mode="ring")
    assert np.array_equal(np.asarray(dr.level), np.asarray(fc.level))
    assert np.array_equal(np.asarray(dr.sigma), np.asarray(fc.sigma))
    assert np.array_equal(np.asarray(dr.scores), np.asarray(fr.scores))
    _row("shard_bc_incr_ring", t_dr * 1e6,
         f"full_ring_us={t_fr * 1e6:.1f};speedup={t_fr / t_dr:.2f}x;"
         f"dirty_frac={frac3:.3f}")
    out["bc"]["ring"] = {"t_delta_s": round(t_dr, 4),
                         "t_full_s": round(t_fr, 4),
                         "speedup_delta_vs_full": round(t_fr / t_dr, 2)}

    # ---- crossover: uniform hot range, growing dirty fraction ---------
    crossover = []
    for frac in fracs:
        g2 = dirty_commit(rng, g, n, frac)
        dirty = dirty_vertices(g, g2)
        view2 = build_sharded_view(g2, mesh)
        t_db, db = _time(delta_bfs_sharded, view2, g2, prior_b, dirty, srcs)
        t_fb, fb = _time(bfs, view2, g2, srcs)
        assert np.array_equal(np.asarray(db.dist), np.asarray(fb.dist))
        t_ds, ds = _time(delta_sssp_sharded, view2, g2, prior_s, dirty, srcs)
        t_fs, fs = _time(sssp, view2, g2, srcs)
        assert np.array_equal(np.asarray(ds.dist), np.asarray(fs.dist))
        crossover.append({
            "dirty_frac": frac,
            "bfs": {"t_delta_s": round(t_db, 4), "t_full_s": round(t_fb, 4),
                    "speedup_delta_vs_full": round(t_fb / t_db, 2)},
            "sssp": {"t_delta_s": round(t_ds, 4), "t_full_s": round(t_fs, 4),
                     "speedup_delta_vs_full": round(t_fs / t_ds, 2)},
        })
    out["crossover"] = crossover
    return out


def bench_service_modes(mesh, g, n, hot_frac, seed, n_commits=6):
    """Mode counters of the sharded service ladder over a commit stream:
    localized hot-range churn submitted through the scheduler, one bfs +
    one sssp query per commit."""
    rng = np.random.default_rng(seed + 2)
    svc = ShardedGraphService(g, mesh, ring_depth=n_commits + 2,
                              batch_size=4096)
    srcs = [0, 1]
    svc.query("bfs", srcs)
    svc.query("sssp", srcs)
    size = max(2, int(n * hot_frac))
    t0 = time.perf_counter()
    for _ in range(n_commits):
        base = int(rng.integers(0, max(1, n - size)))
        ops = []
        for _ in range(size):
            u = base + int(rng.integers(0, size))
            v = int(rng.integers(0, n))
            ops.append((REME, u, v) if rng.random() < 0.3
                       else (PUTE, u, v, float(rng.integers(1, 9))))
        svc.submit_many(ops)
        svc.flush()
        _block(svc.query("bfs", srcs).result)
        _block(svc.query("sssp", srcs).result)
    dt = time.perf_counter() - t0
    st = svc.stats
    modes = {"unchanged": st.unchanged, "delta": st.delta, "full": st.full}
    _row("shard_service_stream", dt / n_commits * 1e6,
         f"unchanged={st.unchanged};delta={st.delta};full={st.full}")
    return modes


def main(a):
    ROWS.clear()
    print("name,us_per_call,derived", flush=True)
    mesh = as_graph_mesh()
    n_dev = int(mesh.devices.size)
    g = load_rmat_graph(a.n, a.n * a.edge_factor, seed=a.seed)

    view, g2, view_stats = bench_view(mesh, g, a.n, a.hot_frac, a.seed)
    n_sources = max(n_dev, a.sources - a.sources % n_dev)
    q = bench_queries(mesh, view, g2, n_sources, a.bc_chunk)
    incr = bench_incremental(mesh, view, g2, a.n, n_sources, a.bc_chunk,
                             a.seed)
    incr["service_modes"] = bench_service_modes(mesh, g2, a.n, a.hot_frac,
                                                a.seed)

    print(f"\nSharded tile grid on {n_dev} devices at n={a.n}: refresh "
          f"{view_stats['refresh_vs_rebuild']:.2f}x over rebuild "
          f"({view_stats['dirty_tile_rows']} dirty tile rows, "
          f"{view_stats['dispatches_batched']} dispatches for "
          f"{view_stats['dispatches_unbatched']} rows); delta at "
          f"{incr['crossover'][0]['dirty_frac'] * 100:.0f}% dirty: bfs "
          f"{incr['bfs']['speedup_delta_vs_full']:.2f}x, sssp "
          f"{incr['sssp']['speedup_delta_vs_full']:.2f}x, bc "
          f"{incr['bc']['speedup_delta_vs_full']:.2f}x over full",
          flush=True)

    from report import bench_metadata
    payload = {
        "bench": "shard",
        "meta": bench_metadata(),
        "backend": jax.default_backend(),
        "devices": n_dev,
        "params": {"n": a.n, "edge_factor": a.edge_factor,
                   "sources": n_sources, "hot_frac": a.hot_frac,
                   "seed": a.seed, "bc_chunk": a.bc_chunk},
        "rows": ROWS,
        "view": view_stats,
        "per_shard_tile_skip_rate":
            view_stats["occupancy"]["per_shard_tile_skip_rate"],
        "queries": q,
        "incremental": incr,
        "speedups": {
            "shardedview_refresh_vs_rebuild":
                view_stats["refresh_vs_rebuild"],
            "sharded_vs_local": {k: v["speedup_sharded_vs_local"]
                                 for k, v in q.items()},
            "sharded_delta_vs_full": {
                k: incr[k]["speedup_delta_vs_full"]
                for k in ("bfs", "sssp", "bc")},
            "bc_ring_vs_gather": q["bc"]["ring"]["ring_vs_gather"],
        },
        "verified": True,  # every timed query is cross-checked above
    }
    if a.json:
        with open(a.json, "w") as f:
            json.dump(payload, f, indent=2)
        print(f"wrote {a.json}", flush=True)
    return payload


if __name__ == "__main__":
    main(ARGS)
