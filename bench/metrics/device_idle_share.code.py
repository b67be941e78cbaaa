"""Engine: share of the traced window in which no operation ran on the
chip (one minus the union of the device's operation intervals)."""


def read(ctx):
    tr = ctx.trace
    win = tr.window() if tr is not None else None
    if win is None:
        return None
    busy = tr.busy_s(win)
    if busy <= 0:
        return None
    return 100.0 * (1.0 - busy / ((win[1] - win[0]) * 1e-9))
