"""Mean time a bucket batched, from its first arrival to its dispatch:
the engine's ``bucket.batch`` spans (``MatFnEngine(trace=True)``)."""


def read(r):
    durs = [s["dur"] for s in r.spans if s["name"] == "bucket.batch"]
    return 1e3 * sum(durs) / len(durs) if durs else None
