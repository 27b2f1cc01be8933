"""decode.hbm_share: bytes the decode steps in the traced window need
(weights once per step, each active row's keys and values up to its
length, the new ones, flops.decode_step) over the device time of the
decode programs times the chip's HBM bandwidth, in percent."""
import flops
import devtrace as trace_lib


def read(ctx):
    t = trace_lib.program_seconds(ctx.trace, ctx.devices, "decode")
    steps = ctx.work["steps"]
    if not t or not steps or ctx.peaks is None:
        return None
    nbytes = sum(flops.decode_step(ctx.model, c)[1] for c in steps.values())
    return 100.0 * nbytes / (t * ctx.peaks["hbm_bytes_per_s"])
