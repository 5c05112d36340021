"""Time to first token at the 90th percentile, seconds, host clock.

Over every request due in the window, counted from its due time; one
with no first token when the window closes counts with its elapsed
time."""

import numpy as np


def read(ctx):
    v = ctx["run"].ttft_s
    return float(np.percentile(v, 90)) if v else None
