"""Share of answers the ladder served from the cache (unchanged) or by a
delta rung, over all answers, from ``ServiceStats`` over the window."""


def read(run):
    c = run.counters
    total = c["unchanged"] + c["delta"] + c["full"]
    return 100.0 * (c["unchanged"] + c["delta"]) / total if total else None
