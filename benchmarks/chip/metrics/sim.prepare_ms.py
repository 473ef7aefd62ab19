"""Host milliseconds per sweep in the program's ``cbp.sweep.prepare`` span
(``repro.sim.sweep``): the plant, the managers' specs, the segment tables
and the stacked grid, up to the call of the stacked program.  Read from
the span's wall total in ``repro.core.dispatch``, which the sweep window
resets; a program without that span reads nothing."""

SPAN = "cbp.sweep.prepare"


def read(record, trace, ctx):
    if record.get("kind") != "sweep" or not record["sweeps"]:
        return None
    try:
        from repro.core.dispatch import span_seconds
    except ImportError:
        return None
    total = span_seconds().get(SPAN)
    return None if total is None else 1e3 * total / record["sweeps"]
