"""Share of the serving cell's slot-steps that processed a token: the
engine's per-stream ``tokens_done`` counters over decode steps times
slots, summed over every call of the window."""


def read(record, trace, ctx):
    if record.get("kind") != "serve":
        return None
    calls = record["calls"]
    steps = sum(c["steps"] for c in calls)
    done = sum(float(c["tokens_done"].sum()) for c in calls)
    return 100.0 * done / (steps * record["slots"]) if steps else None
