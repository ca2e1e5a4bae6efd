"""Mean wait of a dispatched bucket for its execution stream: the
engine's ``stage=queue`` histogram, sum over count inside the window."""


def read(r):
    count, total = r.stages.get("queue", (0, 0.0))
    return 1e3 * total / count if count else None
