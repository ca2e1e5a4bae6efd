"""Mean host time to stack a bucket's operands and look up its
executable: the engine's ``stage=assemble`` histogram in the window."""


def read(r):
    count, total = r.stages.get("assemble", (0, 0.0))
    return 1e3 * total / count if count else None
