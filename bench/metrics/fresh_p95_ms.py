"""95th percentile, over every update op committed in the window and the
commit running at its close, of the time from the op's due time (its slot
in an open-loop schedule, its submit call in a closed loop) until the
commit holding it returned, when that version is the ring's latest and
every query admitted sees it."""
import numpy as np


def read(run):
    done = run.commits + ([run.closing] if run.closing else [])
    fresh = [c.t_ret - due for c in done for due in c.dues]
    return 1e3 * float(np.percentile(fresh, 95)) if fresh else None
