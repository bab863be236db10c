"""Mean time from a request's admission until its batch is staged on the
device: server, scheduler and host staging (``serve/spectral/server.py``,
``scheduler.py``, ``executor._assemble``).  Read from the server's
``queue`` histogram, its sum over its count, differenced across the
window and pooled over the buckets."""


def read(ctx):
    rows = ctx["window"]["counters"].values()
    n = sum(r["queue_n"] for r in rows)
    if not n:
        return None
    return sum(r["queue_sum_s"] for r in rows) / n * 1e3
