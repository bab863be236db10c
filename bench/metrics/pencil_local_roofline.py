"""Share of its roofline that the pencil's local passes reach: the least
time one chip could take for its share of the window's r2c and c2r
transforms (``work.py`` totals over the chips, at the peaks of
``peaks.json``) over that chip's device time outside exposed all-to-all.
``bound`` says whether bytes or operations set that least time."""
import work


def read(ctx):
    red, done = ctx["trace"], ctx["window"]["work"]
    if red is None or not red["devices"] or "r2c" not in done:
        return None
    chips = ctx["cfg"]["chips"]
    share_of_chip = {k: v / chips
                     for k, v in work.add(done["r2c"], done["c2r"]).items()}
    seconds = red["busy_s"] - red["a2a_exposed_s"]
    got = work.roofline(share_of_chip, seconds,
                        work.peaks(ctx["device_kind"]))
    if got is None:
        return None
    share, bound = got
    return {"value": 100.0 * share, "bound": bound, "local_s": seconds}
