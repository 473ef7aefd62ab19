"""Device milliseconds per sweep of the stacked timeline scan: every
manager's Fig. 8 timeline, the interval model and the boundary greedy
inside it.  The scan is the XLA module of the program's jitted ``fn``
(``repro.sim.timeline_jax._compiled_buckets``), read by that name: where a
later program renames or splits it, the metric reads nothing, and says so,
rather than another module's time."""
import sys

MODULE = "jit_fn"


def read(record, trace, ctx):
    if record.get("kind") != "sweep" or trace is None or not record["batches"]:
        return None
    if MODULE not in trace.module_s:
        print(f"sim.scan_device_ms: no XLA module {MODULE!r} in the trace "
              f"(modules: {sorted(trace.module_s)})", file=sys.stderr)
        return None
    return 1e3 * trace.module_s[MODULE] / trace.n_devices / record["batches"]
