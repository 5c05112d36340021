"""Seconds from process start to the window's opening, host clock:
weights, engine, compilation or cache load, warm-up, and the traffic's
own set-up (a closed loop's first prefills)."""


def read(ctx):
    return ctx["run"].setup_s
