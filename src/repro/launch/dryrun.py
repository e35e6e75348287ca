"""Multi-pod dry-run: lower + compile the graph engine's distributed queries
on the production mesh.

The FIRST lines (before any other import, including repro) create 512
placeholder host devices so ``jax.make_mesh`` can build the production mesh
on a CPU-only host.  Never set this flag globally — smoke tests and
benchmarks must see 1 device.

    PYTHONPATH=src python -m repro.launch.dryrun --graph [--both-meshes]

Results are written to experiments/dryrun/graph_engine.<mesh>.json;
``benchmarks/report.py`` tabulates them.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse      # noqa: E402
import json          # noqa: E402
import time          # noqa: E402
import traceback     # noqa: E402

import jax           # noqa: E402

from repro.launch import mesh as meshlib  # noqa: E402
from repro.obs.hlo import parse_collective_bytes  # noqa: E402

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def analyze(compiled) -> dict:
    out = {}
    try:
        ms = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": int(ms.argument_size_in_bytes),
            "output_bytes": int(ms.output_size_in_bytes),
            "temp_bytes": int(ms.temp_size_in_bytes),
            "alias_bytes": int(ms.alias_size_in_bytes),
        }
        out["memory"]["peak_bytes"] = (
            out["memory"]["argument_bytes"] + out["memory"]["output_bytes"]
            + out["memory"]["temp_bytes"] - out["memory"]["alias_bytes"])
    except Exception as e:  # pragma: no cover
        out["memory"] = {"error": str(e)}
    try:
        ca = compiled.cost_analysis()
        out["cost"] = {k: float(v) for k, v in ca.items()
                       if isinstance(v, (int, float))}
    except Exception as e:  # pragma: no cover
        out["cost"] = {"error": str(e)}
    try:
        out["collectives"] = parse_collective_bytes(compiled.as_text())
    except Exception as e:  # pragma: no cover
        out["collectives"] = {"error": str(e)}
    return out


def run_graph_cell(multi_pod: bool, out_dir: str = OUT_DIR,
                   vcap: int = 131072, bc_vcap: int = 16384,
                   n_sources: int = 512) -> dict:
    """The paper's own workload on the production mesh: the sharded
    tile-grid engine's distributed BFS/SSSP/BC over a Table-1-scale graph
    (131072 vertices; the tile grid shards 512 rows of the 64 GiB padded
    weight matrix per chip).  Gather-mode BC all-gathers the row bands per
    shard, so its cell compiles at a smaller vcap; ring-mode BC
    (``bc_ring``, the SUMMA band rotation) keeps per-shard adjacency at
    O(Vp^2/n) and compiles at the FULL vcap like bfs/sssp — note the grid
    pads vcap up to a multiple of tile x n_devices (8 MiB-row granularity
    at 256+ devices), so each cell records the ``vp`` it actually compiled
    at and the per-device numbers must be read against vp, not vcap.
    Collective bytes per level (the O(S x vcap) frontier merges, and the
    ring's O(Vp^2/n) band permutes) land in the ``collectives`` section
    via the HLO parser."""
    from repro.core.partition import (
        make_distributed_query, distributed_query_specs)
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    mesh = meshlib.make_graph_mesh(meshlib.make_production_mesh(
        multi_pod=multi_pod))
    rec = {"arch": "graph_engine", "mesh": mesh_name,
           "vcap": vcap, "bc_vcap": bc_vcap, "n_sources": n_sources,
           "n_devices": int(mesh.devices.size)}
    for query in ("bfs", "sssp", "bc", "bc_ring"):
        v = bc_vcap if query == "bc" else vcap
        fn, in_sh, _ = make_distributed_query(mesh, query)
        sds = distributed_query_specs(v, mesh, n_sources=n_sources)
        t0 = time.time()
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*sds).compile()
        rec[query] = analyze(compiled)
        rec[query]["vcap"] = v
        rec[query]["vp"] = int(sds[0].shape[0])  # padding included
        rec[query]["compile_s"] = round(time.time() - t0, 1)
        del compiled
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"graph_engine.{mesh_name}.json"),
              "w") as f:
        json.dump(rec, f, indent=1)
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--graph", action="store_true",
                    help="run the graph-engine cells")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()
    if not args.graph:
        ap.error("nothing to run: pass --graph")

    failures = []
    for mp in ([False, True] if args.both_meshes else [args.multi_pod]):
        try:
            run_graph_cell(mp, args.out)
            print(f"[graph_engine {'2x16x16' if mp else '16x16'}] ok")
        except Exception as e:
            failures.append(("graph", mp, repr(e)))
            traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall dry-run cells passed")


if __name__ == "__main__":
    main()
