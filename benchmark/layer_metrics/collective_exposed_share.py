"""Percent of the traced window in which a collective operation ran on a
device while no other operation ran on it, on the worst device. Nothing on
one chip."""


def read(r):
    if not r.trace or r.chips == 1:
        return None
    return 100.0 * r.trace["collective_exposed_s"] / r.trace["window_s"]
