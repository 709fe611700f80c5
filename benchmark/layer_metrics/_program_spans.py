"""The program's own work-site spans (`trlx_tpu/obs/spans.py`, written into
the `cycle` rows of the run's flight stream, which `run.Reading.flight` holds)
for the cycles of the window. A program without such spans (the parent of the
PR that added them) gives None, and the metric is left out of the line."""


def window_rows(r, kind="cycle"):
    """The flight stream's rows of `kind` that belong to the window. The
    program closes its `cycle` row just before the harness stamps the
    boundary, so a window cycle and the row that holds its rollout's spans
    and counters carry the same step."""
    steps = {c["step"] for c in r.cycles}
    return [row for row in r.flight if row.get("kind") == kind and row.get("step") in steps]


def span_seconds(r, names):
    """Seconds inside the named spans over the cycles of the window, or
    None where the program wrote no such span."""
    spans = [t1 - t0 for row in window_rows(r) for name, t0, t1, *_ in row.get("spans") or []
             if name in names]
    return sum(spans) if spans else None
