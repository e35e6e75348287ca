"""Exposed collective time per delta or full answer: the device time of the
collective operations (``all-reduce``, ``all-gather``,
``collective-permute``, ... with their ``-start`` / ``-done`` halves) that
no other operation on the same device covers, averaged over the devices,
over the window's delta and full answers (``ServiceStats``)."""


def read(run):
    c = run.counters
    answers = c["delta"] + c["full"]
    if run.trace is None or not run.trace.collectives.ops or not answers:
        return None
    return 1e3 * run.trace.collectives.exposed_s / answers
