"""The whole step's share of the chip's peak FLOP/s, percent: the FLOPs
the traced window's decode calls need (2 per active weight plus
attention, from ``chipbench.counts``) over the traced window's length
times the peak. Layer: whole step."""

from chipbench import counts


def read(ctx):
    pos, tr = ctx.get("positions"), ctx.get("trace")
    if not pos or tr is None or tr.window_s <= 0:
        return None
    flops = sum(counts.call_flops(ctx["shape"], p) for p in pos)
    return 100.0 * flops / (tr.window_s * ctx["peaks"]["bf16_flops_per_s"])
