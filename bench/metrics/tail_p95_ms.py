"""95th percentile of the latency of every request due in the window,
from its due time until its result is on the client (one with no answer
at the longest wait): the tail that queueing behind the host pipeline
builds.  Read from the open-loop client's own clock."""


def read(ctx):
    return ctx["window"]["e2e"].get("latency_p95_ms")
