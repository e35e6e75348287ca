"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Sizes are scaled to this
CPU container (the paper used 56-core Xeons and 10^4-op runs; we keep the
shapes of the curves, not the absolute scale — EXPERIMENTS.md maps each
run back to its figure).
"""
from __future__ import annotations

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np
import jax.numpy as jnp


def _row(name, us, derived=""):
    print(f"{name},{us:.1f},{derived}", flush=True)


# ----- Figures 6/7/8: query latency x graph size x mode -------------------

def fig678_query_latency(sizes=(256, 1024), n_ops=60):
    from workload import load_graph, make_ops, run_mix
    rng = np.random.default_rng(0)
    for query, fig in (("bfs", "fig6"), ("sssp", "fig7"), ("bc", "fig8")):
        for n in sizes:
            g = load_graph(n)
            ops = make_ops(rng, n_ops, n, (0.4, 0.1, 0.5))
            for mode in ("pgcn", "pgicn", "static"):
                r = run_mix(g, ops, query, mode)
                us = r.seconds / max(r.queries, 1) * 1e6
                _row(f"{fig}_{query}_v{n}_{mode}", us,
                     f"queries={r.queries}")


# ----- Figures 9/10/11: workload distributions at fixed size --------------

def fig91011_distributions(n=512, n_ops=80):
    from workload import load_graph, make_ops, run_mix
    rng = np.random.default_rng(1)
    dists = {"40_10_50": (0.4, 0.1, 0.5), "60_10_30": (0.6, 0.1, 0.3),
             "80_10_10": (0.8, 0.1, 0.1)}
    for query, fig in (("bfs", "fig9"), ("sssp", "fig10"), ("bc", "fig11")):
        g = load_graph(n)
        for label, dist in dists.items():
            ops = make_ops(rng, n_ops, n, dist)
            for mode in ("pgcn", "pgicn"):
                r = run_mix(g, ops, query, mode)
                _row(f"{fig}_{query}_{label}_{mode}",
                     r.seconds / max(len(ops), 1) * 1e6,
                     f"total_s={r.seconds:.2f}")


# ----- Figures 12/13: collects per scan + interrupting updates ------------

def fig1213_scan_stats(n=512, n_ops=60):
    from workload import load_graph, make_ops, run_mix
    rng = np.random.default_rng(2)
    for query in ("bfs", "sssp"):
        for label, dist in (("25u", (0.25, 0.25, 0.5)),
                            ("45u", (0.45, 0.05, 0.5))):
            g = load_graph(n)
            ops = make_ops(rng, n_ops, n, dist)
            r = run_mix(g, ops, query, "pgcn")
            per_scan = r.collects / max(r.queries, 1)
            per_q_int = r.interrupts / max(r.queries, 1)
            _row(f"fig12_13_{query}_{label}",
                 r.seconds / max(r.queries, 1) * 1e6,
                 f"collects_per_scan={per_scan:.2f};"
                 f"interrupts_per_query={per_q_int:.2f}")


# ----- Update-throughput microbench (Table-1-scale graphs) ----------------

def bench_update_throughput(n=4096, batch=256, iters=6):
    from repro.core import PUTE, REME, apply_ops
    from workload import load_graph
    rng = np.random.default_rng(3)
    g = load_graph(n)
    ops = [(PUTE, int(rng.integers(0, n)), int(rng.integers(0, n)),
            float(rng.integers(1, 9))) if i % 2 == 0 else
           (REME, int(rng.integers(0, n)), int(rng.integers(0, n)))
           for i in range(batch)]
    g, _ = apply_ops(g, ops, batch_size=batch)       # warm the jit
    t0 = time.perf_counter()
    for _ in range(iters):
        g, _ = apply_ops(g, ops, batch_size=batch)
    g.esrc.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    _row("update_batch256_v4096", dt * 1e6,
         f"ops_per_s={batch / dt:.0f}")


# ----- Kernel sanity timings (jnp oracle path on CPU) ----------------------

def bench_semiring_dense(n=512):
    from repro.core import semiring
    f = jnp.asarray((np.random.default_rng(0).random((n, n)) < 0.01),
                    jnp.float32)
    a = f
    semiring.bool_mm(f, a).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(5):
        semiring.bool_mm(f, a).block_until_ready()
    _row(f"bool_semiring_mm_{n}", (time.perf_counter() - t0) / 5 * 1e6,
         "jnp_path")
    d = jnp.where(f > 0, 1.0, jnp.inf)
    semiring.minplus_mm(d, d).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(3):
        semiring.minplus_mm(d, d).block_until_ready()
    _row(f"minplus_mm_{n}", (time.perf_counter() - t0) / 3 * 1e6,
         "jnp_path")


def main() -> None:
    print("name,us_per_call,derived")
    fig678_query_latency()
    fig91011_distributions()
    fig1213_scan_stats()
    bench_update_throughput()
    bench_semiring_dense()


if __name__ == "__main__":
    main()
