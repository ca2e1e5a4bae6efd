"""Device busy time in the traced window over the answers completed in
it (profiler trace)."""


def read(r):
    if r.device is None or not r.answers:
        return None
    return 1e3 * r.device.busy_s / r.answers
