"""Device programs the evaluator's host loop launches per sweep, from the
program's own ``repro.core.dispatch`` counter."""


def read(record, trace, ctx):
    if record.get("kind") != "sweep" or not record["batches"]:
        return None
    return record["dispatches"] / record["batches"]
