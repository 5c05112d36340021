"""Host time the engine spends composing rounds, per ``step()``,
milliseconds: the program's ``phase_compose`` timer total over its
``engine_steps`` counter, both over the traced window.
Layer: serve.composer."""


def read(ctx):
    c = ctx.get("counters")
    if not c or not c["engine_steps"]:
        return None
    return 1e3 * c["phase_compose_s"] / c["engine_steps"]
