"""XLA module executions per sweep on each device, from the trace's
``XLA Modules`` line: what the chip ran, beside ``sim.programs_per_batch``
(the program's own count of its jitted entries).  This one counts the
small eager programs outside those entries too, such as the per-spec
slices of the stacked program's outputs."""


def read(record, trace, ctx):
    if record.get("kind") != "sweep" or trace is None or not record["sweeps"]:
        return None
    return (sum(trace.module_calls.values()) / trace.n_devices
            / record["sweeps"])
