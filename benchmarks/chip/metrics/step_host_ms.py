"""step_host_ms: the host's own work in one engine iteration: the mean,
over the program's ``engine.step`` spans inside the traced window, of
the span's duration less the part of it that ``engine.fetch`` spans
(the waits for token ids) cover, in milliseconds.  A program without
such spans reads nothing."""
import bisect

import devtrace as trace_lib


def read(ctx):
    tr = ctx.trace
    lo, hi = tr.window
    steps = [s for s in tr.host
             if s[0] == "engine.step" and lo <= s[1] and s[2] <= hi]
    if not steps:
        return None
    fetches = sorted((s for s in tr.host if s[0] == "engine.fetch"),
                     key=lambda s: s[1])
    starts = [s[1] for s in fetches]
    own = 0.0
    for _, s, e in steps:
        inside = fetches[bisect.bisect_left(starts, s):
                         bisect.bisect_left(starts, e)]
        own += (e - s) - sum(b - a for a, b in trace_lib.union(inside, s, e))
    return 1e-6 * own / len(steps)
