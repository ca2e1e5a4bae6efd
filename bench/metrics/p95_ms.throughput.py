"""95th percentile of latency from submit over every request due in the
traced window, on the host clock: the tail of a closed-loop round, which
sets when each client starts its next round. A host stall moves it too
widely from run to run to carry an end-to-end bound."""


def read(r):
    return r.latency.p95_ms
