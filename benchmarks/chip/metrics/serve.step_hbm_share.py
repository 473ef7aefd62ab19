"""The whole decode step's share of the chip's HBM bandwidth: the least
bytes of a step (``bench/hybrid_counts.py``: every weight once, and for
each slot-step that processed a token its recurrent state read and
written and its filled K/V) over the device time per step of the interval
program (``bench/serve_step.py``), over the published bytes/s.  Reads
nothing where the cell's entry left no byte count."""
from bench import serve_step


def read(record, trace, ctx):
    s = serve_step.device_s(record, trace)
    w = record.get("work", {})
    if s is None or ctx.peaks is None or "bytes" not in w:
        return None
    return 100.0 * w["bytes"] / w["steps"] / (s * ctx.peaks.hbm_bytes_per_s)
