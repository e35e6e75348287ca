"""Device time of the update kernel (``core/updates.apply_batch``, traced
as ``jit_apply_batch``) per commit in the traced window."""
PROGRAM = "jit_apply_batch"


def read(run):
    if run.trace is None or not run.commits:
        return None
    secs = run.trace.programs.get(PROGRAM, 0.0)
    return 1e3 * secs / len(run.commits) if secs > 0 else None
