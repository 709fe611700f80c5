"""Arithmetic shared by the span readers."""


def phase_share(r, *phases):
    """Percent of the window's host wall inside the named phases."""
    return 100.0 * sum(r.phases.get(p, 0.0) for p in phases) / r.wall_s
