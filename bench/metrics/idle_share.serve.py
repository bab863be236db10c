"""Share of the served window in which no operation ran on the device:
one minus the union of the op intervals in the profiler trace over the
window's host-clock length (first due time to last result)."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red["devices"] or red["idle_share"] is None:
        return None
    return 100.0 * red["idle_share"]
