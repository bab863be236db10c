"""Mean time from a batch being staged until its results are on the host:
dispatch, device and copy-back (``serve/spectral/executor.py``).  Read
from the server's ``service`` histogram, its sum over its count (one
observation per request), differenced across the window and pooled over
the buckets."""


def read(ctx):
    rows = ctx["window"]["counters"].values()
    n = sum(r["service_n"] for r in rows)
    if not n:
        return None
    return sum(r["service_sum_s"] for r in rows) / n * 1e3
