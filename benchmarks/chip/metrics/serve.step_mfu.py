"""The whole decode step's share of the chip's bf16 peak: the model
operations of every slot-step that processed a token
(``bench/model_counts.py``) per decode step, over the device time per
step of the interval program (``bench/serve_step.py``)."""
from bench import serve_step


def read(record, trace, ctx):
    s = serve_step.device_s(record, trace)
    if s is None or ctx.peaks is None or "work" not in record:
        return None
    w = record["work"]
    return 100.0 * w["flops"] / w["steps"] / (s * ctx.peaks.flops_bf16)
