"""Mean host time inside ``MatFnEngine.submit``: the engine's
``stage=submit`` histogram, sum over count inside the window (a tracing
engine records it; otherwise nothing is read)."""


def read(r):
    count, total = r.stages.get("submit", (0, 0.0))
    return 1e6 * total / count if count else None
