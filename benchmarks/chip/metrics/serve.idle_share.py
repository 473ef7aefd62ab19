"""Share of the serving cell's traced window in which no operation ran on
the device (averaged over the cell's chips)."""


def read(record, trace, ctx):
    if record.get("kind") != "serve" or trace is None:
        return None
    return 100.0 * (1.0 - trace.busy_s / ctx.window_s)
