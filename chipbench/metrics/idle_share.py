"""Share of the window in which no operation ran on the device: one
less the union of the device's op intervals over the window, averaged
over the chips the cell uses."""

UNIT, LAYER, MOVES = "%", "device", "updates_per_s"


def read(ctx):
    if not ctx.devices or ctx.window_s <= 0:
        return None
    busy = sum(d["busy_ns"] for d in ctx.devices) / len(ctx.devices) / 1e9
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / ctx.window_s)
