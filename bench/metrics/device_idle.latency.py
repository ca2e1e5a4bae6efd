"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window (profiler trace)."""


def read(r):
    return None if r.device is None else 100.0 * r.device.idle_share
