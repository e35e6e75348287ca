"""Device time of the served rung programs in the traced window, per
delta or full answer.  The rungs are ``jax.jit`` of lambdas
(``serve/batch.py``), so every rung of every kind runs as ``jit__lambda``."""
RUNG_PROGRAMS = ("jit__lambda",)


def read(run):
    c = run.counters
    answers = c["delta"] + c["full"]
    if run.trace is None or not answers:
        return None
    secs = sum(run.trace.programs.get(p, 0.0) for p in RUNG_PROGRAMS)
    return 1e3 * secs / answers if secs > 0 else None
