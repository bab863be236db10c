"""All-to-all device time per step, averaged over the chips: the pencil
exchanges' ``all-to-all`` ops in the profiler trace over the window's
steps."""


def read(ctx):
    red, steps = ctx["trace"], ctx["window"]["diag"].get("steps")
    if red is None or not red["devices"] or not red["a2a_s"] or not steps:
        return None
    return 1e3 * red["a2a_s"] / steps
