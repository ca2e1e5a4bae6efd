"""95th percentile of latency from due over every request due in the
traced window, on the host clock: the open-loop tail. A host stall moves
it too widely from run to run to carry an end-to-end bound."""


def read(r):
    return r.latency.p95_ms
