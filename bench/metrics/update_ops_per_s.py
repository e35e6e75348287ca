"""Update ops committed per second: every op whose commit was called in the
window, over the window or, where a commit was still running at its
close, up to that commit's return.

A commit that straddles the window's end is counted with its ops and its
time, so a stall there always lands inside the span; the closed-loop
writer's commits are few and even (about 70 of 0.71 s at 2^20 vertices),
and the rate then moves with their length continuously rather than by a
whole commit.
"""


def read(run):
    done = run.commits + ([run.closing] if run.closing else [])
    if not done:
        return None
    span = max(run.seconds, done[-1].t_ret - run.t0)
    return sum(len(c.dues) for c in done) / span
