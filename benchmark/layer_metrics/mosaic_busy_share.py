"""Percent of device busy time spent in Mosaic (pallas) custom calls, by
whatever names the trace gives them (see trace_reduce.is_mosaic)."""


def read(r):
    if not r.trace or r.trace["busy_s"] <= 0:
        return None
    return 100.0 * r.trace["mosaic_s"] / r.trace["busy_s"]
