"""Device time of the sharded query programs in the traced window, per
delta or full answer, averaged over the devices of the mesh.  Each is one
``shard_map`` program of ``shard/queries.py``, jitted under the name of its
body: the full BFS, SSSP and ring BC, and their delta forms."""
PROGRAMS = ("jit__bfs_body", "jit__sssp_body", "jit__bc_ring_body",
            "jit__bfs_delta_body", "jit__sssp_delta_body",
            "jit__bc_delta_ring_body")


def read(run):
    c = run.counters
    answers = c["delta"] + c["full"]
    if run.trace is None or not answers:
        return None
    secs = sum(run.trace.programs.get(p, 0.0) for p in PROGRAMS)
    return 1e3 * secs / answers if secs > 0 else None
