"""Mean host-clock time of the updater's submit calls that committed a
batch (the scheduler's apply, the overflow check and the ring append)."""


def read(run):
    if not run.commits:
        return None
    return 1e3 * sum(c.t_ret - c.t_call for c in run.commits) / len(
        run.commits)
