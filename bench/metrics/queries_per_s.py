"""Queries answered in the window, per second of the window."""


def read(run):
    if not run.queries:
        return None
    return sum(not q.failed for q in run.queries) / run.seconds
