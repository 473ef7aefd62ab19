"""Device time per decode step of the serving engine, for the serving
cells' readers.

The engine runs one XLA module per reconfiguration interval: the jitted
``JitServingEngine._interval``, a scan over the interval's decode steps
with admission and the in-trace CBP reconfigure.  It is read by that name;
where a later program renames or splits it, the readers read nothing, and
say so, rather than another module's time.  Each recorded execution
counts for the steps an interval ran in the window, so a trace that kept
only some of the executions still gives the time of a step.
"""
from __future__ import annotations

import sys
from typing import Optional

MODULE = "jit__interval"


def device_s(record, trace) -> Optional[float]:
    """Device seconds per decode step, or ``None``."""
    if record.get("kind") != "serve" or trace is None:
        return None
    if not trace.module_calls.get(MODULE):
        print(f"serving readers: no XLA module {MODULE!r} in the trace "
              f"(modules: {sorted(trace.module_s)})", file=sys.stderr)
        return None
    calls = record["calls"]
    steps_per_exec = (sum(c["steps"] for c in calls)
                      / sum(c["intervals"] for c in calls))
    return trace.module_s[MODULE] / (trace.module_calls[MODULE]
                                     * steps_per_exec)
