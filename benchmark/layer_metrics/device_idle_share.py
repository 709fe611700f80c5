"""Percent of the traced window in which no operation ran on the device
(1 - union of device-op intervals over the window, mean over the chips)."""


def read(r):
    if not r.trace:
        return None
    return 100.0 * (1.0 - r.trace["busy_s"] / r.trace["window_s"])
