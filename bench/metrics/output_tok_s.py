"""output_tok_s: output tokens emitted in the window over its seconds.

In a backlog, whose requests have the window's end as their deadline,
the window closes when the step in flight at that end has returned: the
engine's first look at the clock past the end truncates every request,
so that step is its last.  All of its tokens count, over all the time
to its return, and no step is cut in two at the end."""


def read(run):
    lo = run.window.t0
    hi = lo + run.seconds
    if run.window.backlog:
        hi = max([hi] + [t for r in run.recs for t in r.times])
    n = sum(1 for r in run.window.recs.values() for t in r.times
            if lo <= t <= hi)
    return n / (hi - lo)
