"""Share of its roofline the serving program reaches, percent.

For the decode calls of the traced window: the sum of each call's least
time (the larger of its FLOPs over peak and its bytes over peak, as the
configuration's family counts them, ``work`` of ``chipbench/reference/
<model_type>.py``: only the weights the call needs, top-k experts only,
and the cache up to its position) over the device time of the
program's module events. Layer: decode_step program (XLA)."""

from chipbench import counts

#: the program's jitted serving step, as the device trace names it
PROGRAM = "jit_decode_step"


def read(ctx):
    tr, pos = ctx.get("trace"), ctx.get("positions")
    if tr is None or not pos or not tr.programs.get(PROGRAM):
        return None
    if tr.program_calls[PROGRAM] != len(pos):
        return None                    # the calls are not the ones counted
    least = sum(counts.least_time(ctx["work"], p, ctx["peaks"])
                for p in pos)
    return 100.0 * least / tr.programs[PROGRAM]
