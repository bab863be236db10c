"""Share of the dispatched batch slots that carried a request
(``scheduler.py``): batch items over batches times ``max_batch``, from
the server's counters differenced across the window, pooled over the
buckets."""


def read(ctx):
    rows = ctx["window"]["counters"].values()
    slots = sum(r["batches"] * r["max_batch"] for r in rows)
    if not slots:
        return None
    return 100.0 * sum(r["batch_items"] for r in rows) / slots
