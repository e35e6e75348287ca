"""Share of the requests the front end picked up in the window that it
re-pinned to the latest version at dispatch, because their group was
admitted behind it: the ``lanes`` (requests) of the window's
``repro.repin`` spans over ``ServeStats.picked``.  A program whose
collects leave no ``repro.collect`` span has no re-pin, and reads
nothing."""


def read(run):
    c = run.counters
    if run.spans is None or "collect" not in run.spans.count \
            or not c["picked"]:
        return None
    return 100.0 * run.spans.lanes.get("repin", 0) / c["picked"]
