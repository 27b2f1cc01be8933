"""decode.mfu: model operations of the decode steps in the traced
window (active rows only, flops.decode_step) over the device time of
the decode programs times the chip's bf16 peak, in percent."""
import flops
import devtrace as trace_lib


def read(ctx):
    t = trace_lib.program_seconds(ctx.trace, ctx.devices, "decode")
    steps = ctx.work["steps"]
    if not t or not steps or ctx.peaks is None:
        return None
    ops = sum(flops.decode_step(ctx.model, c)[0] for c in steps.values())
    return 100.0 * ops / (t * ctx.peaks["bf16_flops"])
