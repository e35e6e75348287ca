"""Host time of the sharded tile view's refresh per commit in the traced
window: the ``repro.tile_refresh`` spans (``ShardedGraphService.view``:
the dirty tile rows read back, the row windows planned, the refresh
programs launched) over the commits of the window."""


def read(run):
    if run.spans is None or not run.commits:
        return None
    secs = run.spans.self_s.get("tile_refresh", 0.0)
    return 1e3 * secs / len(run.commits) if secs > 0 else None
