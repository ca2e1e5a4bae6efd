"""Mean time from a bucket's dispatch to its outputs being ready on the
device: the engine's ``stage=device`` histogram, sum over count inside
the window (a tracing engine records it; otherwise nothing is read)."""


def read(r):
    count, total = r.stages.get("device", (0, 0.0))
    return 1e3 * total / count if count else None
