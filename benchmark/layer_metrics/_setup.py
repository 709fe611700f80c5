"""What the program itself recorded of its set-up (`trlx_tpu/obs/`): the
`setup` row of the run's flight stream (the constructor's spans, `init_s`,
`since_import_s`, every compile named and marked read or built) and the
`cycle` rows closed before the window opened (the warm-up blocks, whose
`compile_totals` hold what they compiled). A program that writes no `setup`
row (the parent of the PR that added it) gives None, and the metric is left
out of the line."""

TOTALS = ("trace_s", "lower_s", "build_s", "read_s")  # what `compile_s` sums, from outside


def before_window(r):
    """`(setup rows, cycle rows closed before the window)`, or None where the
    stream has no `setup` row. The program closes a `cycle` row just before
    the harness stamps the boundary, so the warm-up rows are those whose step
    is below the first window cycle's."""
    setup = [row for row in r.flight if row.get("kind") == "setup"]
    if not setup:
        return None
    opens = min(c["step"] for c in r.cycles)
    warm = [row for row in r.flight if row.get("kind") == "cycle"
            and row.get("step") is not None and row["step"] < opens]
    return setup, warm


def compiled(r, *keys):
    """The sum of the named compile totals before the window, or None."""
    rows = before_window(r)
    if rows is None:
        return None
    setup, warm = rows
    totals = [row.get("compiles") or {} for row in setup]
    totals += [row.get("compile_totals") or {} for row in warm]
    return sum(t.get(k, 0) for t in totals for k in keys)


def span_seconds(r, *names):
    """Seconds inside the named spans of the `setup` rows, or None."""
    rows = before_window(r)
    if rows is None:
        return None
    return sum(t1 - t0 for row in rows[0] for name, t0, t1, *_ in row.get("spans") or []
               if name in names)
