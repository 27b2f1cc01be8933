"""dsv2_decode.hbm_share: bytes the decode steps in the traced window
need (flops_mla_moe.decode_step: weights outside the routed experts
once, each held expert a pair reached, each active row's latent cache to
its length and its new entry, its logits) over the device time of the
engine's jitted ``decode_step`` (found by module name) times the chip's
HBM bandwidth, in percent.  Held experts reached per step: the engines'
routing counters over every decode step of the run
(``moe_experts_hit / moe_steps``).  A program without those counters
reads nothing."""
import flops_mla_moe
import modules
import moe_counters


def read(ctx):
    t = modules.seconds(ctx.trace, ctx.devices, "decode_step")
    rates = moe_counters.per_step(ctx.rec)
    steps = ctx.work["steps"]
    if not t or not steps or rates is None or ctx.peaks is None:
        return None
    pairs_per_row, hit_per_step = rates
    nbytes = sum(flops_mla_moe.decode_step(
        ctx.model, c, pairs_per_row * len(c), hit_per_step)[1]
        for c in steps.values())
    return 100.0 * nbytes / (t * ctx.peaks["hbm_bytes_per_s"])
