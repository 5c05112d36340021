"""The whole step's share of the chip's peak FLOP/s, percent: the FLOPs
the traced window's decode calls need (2 per active weight plus
attention, as the configuration's family counts them: ``work`` of
``chipbench/reference/<model_type>.py``) over the traced window's
length times the peak. Layer: whole step."""

from chipbench import counts


def read(ctx):
    pos, tr = ctx.get("positions"), ctx.get("trace")
    if not pos or tr is None or tr.window_s <= 0:
        return None
    flops = sum(counts.call_flops(ctx["work"], p) for p in pos)
    return 100.0 * flops / (tr.window_s * ctx["peaks"]["bf16_flops_per_s"])
