"""Emit the graph-engine dry-run table (``launch/dryrun.py --graph``
artifacts), plus the shared ``bench_metadata()`` header every BENCH_*.json
emitter stamps into its payload (schema version, git sha, device
inventory)."""
import datetime
import glob
import json
import os
import subprocess

BENCH_SCHEMA_VERSION = 1

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                          "dryrun")


def _git_sha():
    try:
        return subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=os.path.dirname(os.path.abspath(__file__)),
            capture_output=True, text=True, timeout=10,
        ).stdout.strip() or None
    except Exception:
        return None


def bench_metadata():
    """The provenance header shared by every BENCH_*.json payload.

    One place defines the schema, so the CI gates (and any diffing of
    bench artifacts across commits) can rely on every emitter carrying
    the same ``meta`` block.
    """
    import jax
    return {
        "bench_schema": BENCH_SCHEMA_VERSION,
        "git_sha": _git_sha(),
        "jax_version": jax.__version__,
        "backend": jax.default_backend(),
        "device_count": jax.device_count(),
        "timestamp": datetime.datetime.now(
            datetime.timezone.utc).isoformat(timespec="seconds"),
    }


def graph_table():
    out = ["| cell | mesh | query | per-level coll | flops(body) | compile |",
           "|---|---|---|---|---|---|"]
    for fn in sorted(glob.glob(os.path.join(DRYRUN_DIR,
                                            "graph_engine*.json"))):
        rec = json.load(open(fn))
        name = os.path.basename(fn).replace(".json", "")
        for q in ("bfs", "sssp"):
            if q not in rec:
                continue
            c = rec[q]["collectives"]
            out.append(
                f"| {name} | {rec['mesh']} | {q} | "
                f"{c.get('total', 0)/1024:.0f} KiB/{c.get('count')} ops | "
                f"{rec[q]['cost'].get('flops', 0):.2e} | "
                f"{rec[q].get('compile_s', '?')}s |")
    return "\n".join(out)


if __name__ == "__main__":
    print(graph_table())
