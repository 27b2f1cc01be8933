"""fetch_idle_share: the share of the traced window in which a chip runs
no operation while the host is inside an ``engine.fetch`` span (waiting
for token ids, of any replica), averaged over the cell's chips, in
percent: the part of idle_share that the host's sync accounts for.  A
program without such spans reads nothing."""
import devtrace as trace_lib


def _overlap_ns(xs, ys) -> float:
    """Total overlap of two sorted lists of disjoint intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        total += max(0.0, min(xs[i][1], ys[j][1]) - max(xs[i][0], ys[j][0]))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx):
    tr = ctx.trace
    lo, hi = tr.window
    if hi <= lo or not any(tr.ops.get(d) for d in ctx.devices):
        return None
    fetch = trace_lib.union([s for s in tr.host if s[0] == "engine.fetch"],
                            lo, hi)
    if not fetch:
        return None
    idle = [_overlap_ns(trace_lib.idle_gaps(tr, d), fetch) / (hi - lo)
            for d in ctx.devices]
    return 100.0 * sum(idle) / len(idle)
