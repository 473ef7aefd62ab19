"""Boundary-greedy body applications per sweep (four per while-loop trip,
summed over every reconfiguration boundary of the stacked programs), from
the program's ``repro.core.dispatch.greedy_trips`` counter, which the
sweep window resets; a program without that counter reads nothing."""


def read(record, trace, ctx):
    if record.get("kind") != "sweep" or not record["sweeps"]:
        return None
    try:
        from repro.core.dispatch import greedy_trips
    except ImportError:
        return None
    return greedy_trips() / record["sweeps"]
