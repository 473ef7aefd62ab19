"""Host milliseconds per sweep in the program's ``cbp.sweep.collect`` span
(``repro.sim.sweep``): from the return of the stacked program's call, the
per-spec slices of its outputs, their fetch to the host, the capacity
checks and the mean IPC.  Read from the span's wall total in
``repro.core.dispatch``, which the sweep window resets; a program without
that span reads nothing."""

SPAN = "cbp.sweep.collect"


def read(record, trace, ctx):
    if record.get("kind") != "sweep" or not record["sweeps"]:
        return None
    try:
        from repro.core.dispatch import span_seconds
    except ImportError:
        return None
    total = span_seconds().get(SPAN)
    return None if total is None else 1e3 * total / record["sweeps"]
