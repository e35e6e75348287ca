"""Lanes per compiled dispatch of the async front end: delta and full
answers over ``ServeStats.dispatches``, both counted over the window."""


def read(run):
    c = run.counters
    return (c["delta"] + c["full"]) / c["dispatches"] if c["dispatches"] \
        else None
