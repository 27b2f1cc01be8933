"""tick_ms: mean wall milliseconds of one ``cluster.advance`` (every
replica stepped up to the gateway's next tick) in the window."""


def read(ctx):
    rec = ctx.rec
    ks = [k for k in range(len(rec.adv_end)) if rec.in_window(k)]
    if not ks:
        return None
    return 1e3 * sum(rec.adv_end[k] - rec.adv_start[k] for k in ks) / len(ks)
