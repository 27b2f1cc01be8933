"""idle_share: the share of the traced window in which no operation ran
on a chip, averaged over the cell's chips, in percent."""
import devtrace as trace_lib


def read(ctx):
    tr = ctx.trace
    lo, hi = tr.window
    if hi <= lo or not any(tr.ops.get(d) for d in ctx.devices):
        return None
    busy = [trace_lib.busy_ns(tr, d) / (hi - lo) for d in ctx.devices]
    return 100.0 * (1.0 - sum(busy) / len(busy))
