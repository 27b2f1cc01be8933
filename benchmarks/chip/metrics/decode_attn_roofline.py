"""decode_attn_roofline: the decode-attention kernel's share of its
roofline in the traced window: the least time its calls need (per
layer and step, the larger of operations over peak and bytes over
bandwidth for the active rows up to their lengths,
flops.decode_attention) over the kernel's device time, in percent."""
import flops
import devtrace as trace_lib


def read(ctx):
    t = trace_lib.kernel_seconds(ctx.trace, ctx.devices, "decode")
    steps = ctx.work["steps"]
    if not t or not steps or ctx.peaks is None:
        return None
    layers = int(ctx.model["num_hidden_layers"])
    need = sum(layers * flops.least_time(
        *flops.decode_attention(ctx.model, c), ctx.peaks)
        for c in steps.values())
    return 100.0 * need / t
