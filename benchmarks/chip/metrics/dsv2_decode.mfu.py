"""dsv2_decode.mfu: useful operations of the decode steps in the traced
window (flops_mla_moe.decode_step: active rows' projections, absorbed
scores and values over each row's own length, the (token, expert) pairs
that reached a held expert, shared experts, router and head) over the
device time of the engine's jitted ``decode_step`` (found by module
name) times the chip's bf16 peak, in percent.  Pairs per row: the
engines' routing counters over every decode step of the run
(``moe_held_pairs / (moe_steps x n_slots)``).  A program without those
counters reads nothing."""
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
    ops = sum(flops_mla_moe.decode_step(
        ctx.model, c, pairs_per_row * len(c), hit_per_step)[0]
        for c in steps.values())
    return 100.0 * ops / (t * ctx.peaks["bf16_flops"])
