"""route_ms: mean wall milliseconds of one routing decision
(``policy.route``, stamped by the harness's wrapper) in the window."""


def read(ctx):
    rec = ctx.rec
    calls = [d for t, d in ctx.policy.calls
             if rec.t_open < t and t + d <= rec.t_close]
    return 1e3 * sum(calls) / len(calls) if calls else None
