"""The longest time in the window in which no result landed on the
client: a stall of the server, or of the whole process, shows here; in a
calm window it is about the time between two batches' results.  Read
from the open-loop client's clock."""


def read(ctx):
    gap = ctx["window"]["diag"].get("longest_result_gap")
    return None if gap is None else gap["ms"]
