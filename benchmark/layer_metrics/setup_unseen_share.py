"""Percent of `setup_s` the program's tracing does not see: `setup_s` less
`since_import_s + init_s` (the package's import to the constructor's
return), less the `prompt_pipeline` spans, less the walls of the `cycle`
rows closed before the window. What is left is the interpreter and the
imports before `import trlx_tpu`, the harness's own work outside the
program, and the wait for the last warm-up block. Never negative."""

from benchmark.layer_metrics import _setup


def read(r):
    rows = _setup.before_window(r)
    if rows is None:
        return None
    setup, warm = rows
    first = setup[0]
    if "since_import_s" not in first or "init_s" not in first:
        return None  # a trainer built outside `trlx_tpu.train()`
    seen = (first["since_import_s"] + first["init_s"]
            + _setup.span_seconds(r, "prompt_pipeline")
            + sum(row.get("wall_s", 0.0) for row in warm))
    return 100.0 * max(r.setup_s - seen, 0.0) / r.setup_s
