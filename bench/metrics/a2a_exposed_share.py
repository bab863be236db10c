"""Share of the window in which an all-to-all runs on a chip and no other
op runs there, averaged over the chips: the exchange time that compute
does not hide."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red["devices"] or not red["a2a_s"] \
            or not red["window_s"]:
        return None
    return 100.0 * red["a2a_exposed_s"] / red["window_s"]
