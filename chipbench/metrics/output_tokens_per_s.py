"""Output tokens completed in the window over the window, host clock.

The window runs from its opening to the end of the last step that
started in it; every token a step of the window returned counts."""


def read(ctx):
    run = ctx["run"]
    return run.tokens / run.window_s if run.window_s > 0 else None
