"""Gap between successive output tokens at the 95th percentile,
seconds, host clock.

Over every gap that began in the window; a request still live at the
close adds its open gap."""

import numpy as np


def read(ctx):
    v = ctx["run"].itl_s
    return float(np.percentile(v, 95)) if v else None
