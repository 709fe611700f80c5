"""Arithmetic shared by the readers of device seconds by scope and by program."""


def busy_share(trace, seconds):
    """Percent of the traced window's device busy time, or None where there
    is nothing to read: no such scope or program in the trace."""
    if not seconds or trace["busy_s"] <= 0:
        return None
    return 100.0 * seconds / trace["busy_s"]
