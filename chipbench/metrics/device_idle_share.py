"""device_idle_share: the share of the traced window in which no operation
ran on the device (busy is the union of the op intervals)."""


def read(ctx):
    red = ctx["trace"]
    if red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
