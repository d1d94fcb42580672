"""device.idle_share: the share of the traced window in which no operation
ran on the device (1 minus the union of the op intervals), in %."""


def read(r):
    if r.window_s <= 0 or r.busy_s <= 0:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
