"""Host time of the engine's execution phase per model position
processed (a replayed prompt token or a decoded token), milliseconds:
the program's ``phase_execute`` timer total over the traced window,
over the decode calls the harness saw the requests' states make.
Layer: serve.engine."""


def read(ctx):
    c, pos = ctx.get("counters"), ctx.get("positions")
    if not c or not pos:
        return None
    return 1e3 * c["phase_execute_s"] / len(pos)
