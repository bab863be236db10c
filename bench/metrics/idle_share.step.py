"""Share of the closed-loop window in which no operation ran on the
device, averaged over the chips: one minus the union of the op intervals
in the profiler trace over the window's host-clock length."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red["devices"] or red["idle_share"] is None:
        return None
    return 100.0 * red["idle_share"]
