"""queue_wait_p90_ms: 90th percentile of the wall time a request waits
from the gateway putting it into the cluster's queue (the program's
``Request.wall_queued``) until an engine gives it a slot
(``wall_started``), over the requests given their slot inside the
window.  A program that keeps no such stamps reads nothing."""
import endtoend


def read(ctx):
    rec = ctx.rec
    waits = []
    for req in rec.requests.values():
        queued = getattr(req, "wall_queued", None)
        started = getattr(req, "wall_started", None)
        if queued is not None and started is not None \
                and rec.t_open <= started <= rec.t_close:
            waits.append(started - queued)
    return 1e3 * endtoend.pct(waits, 90) if waits else None
