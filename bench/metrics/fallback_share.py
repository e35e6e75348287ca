"""Share of the requests the front end picked up in the window that it
served on the per-request resilient path (``ServeStats.fallbacks`` over
``ServeStats.picked``): for the sharded service, the groups pinned to a
version behind the latest."""


def read(run):
    c = run.counters
    return 100.0 * c["fallbacks"] / c["picked"] if c["picked"] else None
