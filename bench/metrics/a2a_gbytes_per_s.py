"""Off-chip exchange bytes per chip (the program's wire log per step times
the (p-1)/p share that leaves the chip, times the steps) over the chip's
all-to-all device time in the trace."""


def read(ctx):
    red = ctx["trace"]
    moved = ctx["window"].get("counters", {}).get("exchange_offchip_bytes")
    if red is None or not red["devices"] or not red["a2a_s"] or not moved:
        return None
    return moved / red["a2a_s"] / 1e9
