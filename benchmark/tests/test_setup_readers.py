"""The five readers of the program's own set-up record on a hand-made
`Reading`: a `setup` row and two warm-up `cycle` rows before the window's."""

import pytest

from benchmark.layer_metrics import (
    setup_built_s, setup_compile_share, setup_init_share, setup_programs_built,
    setup_unseen_share)
from benchmark.tests.test_layer_readers import reading

READERS = (setup_compile_share, setup_programs_built, setup_built_s, setup_init_share,
           setup_unseen_share)

SETUP = {
    "kind": "setup", "cycle": 1, "step": 0, "since_import_s": 6.0, "init_s": 30.0,
    "spans": [["ref_init", 14.0, 16.0, "model_init", {"params": 5}],
              ["model_init", 0.0, 16.0, None, {"params": 9}],
              ["opt_init", 16.0, 20.0, None, {}],
              ["router_balance", 31.0, 35.0, "prompt_pipeline", {"steps": 24}],
              ["prompt_pipeline", 30.5, 36.0, None, {"prompts": 64}],
              ["prompt_pipeline", 36.0, 36.5, None, {"prompts": 8}]],
    "compiles": {"requests": 100, "built": 90, "read": 10, "written": 0, "trace_s": 2.0,
                 "lower_s": 3.0, "build_s": 10.0, "read_s": 1.0, "built_s": 14.0},
    "programs": [["jit_add", 100, 16.0, 90]], "by_span": {"model_init": [16.0, 90]},
}


def warm(step, wall_s, **totals):
    row = {"kind": "cycle", "step": step, "wall_s": wall_s, "spans": []}
    if totals:
        full = dict.fromkeys(SETUP["compiles"], 0)
        row.update(compile_totals=dict(full, **totals), compiles=[["jit_x", 0.0, 1.0, True, None]])
    return row


def flight(setup=SETUP):
    rows = [{"kind": "run_start", "step": 0},
            warm(4, 20.0, requests=12, built=2, read=10, trace_s=0.5, lower_s=0.5, build_s=1.0,
                 read_s=6.0, built_s=1.5),
            warm(8, 8.0, requests=6, built=6, lower_s=0.25, build_s=0.75, built_s=1.0),
            # the window's cycles: what they compiled is not set-up
            warm(12, 7.0, requests=1, built=1, build_s=50.0, built_s=50.0),
            warm(16, 7.0)]
    return ([setup] if setup else []) + rows


def test_the_five_readers_on_a_setup_row_and_two_warm_up_cycles():
    r = reading(flight=flight())
    r.setup_s = 80.0
    # (2 + 3 + 10 + 1) + (0.5 + 0.5 + 1 + 6) + (0.25 + 0.75) of 80 s
    assert setup_compile_share.read(r) == pytest.approx(100 * 25.0 / 80.0)
    assert setup_programs_built.read(r) == 90 + 2 + 6
    assert setup_built_s.read(r) == pytest.approx(14.0 + 1.5 + 1.0)
    # model_init 16 (ref_init inside it), opt_init 4, router_balance 4
    assert setup_init_share.read(r) == pytest.approx(100 * 24.0 / 80.0)
    # 80 - (6 + 30) - (5.5 + 0.5) - (20 + 8)
    assert setup_unseen_share.read(r) == pytest.approx(100 * 10.0 / 80.0)


def test_the_windows_compiles_are_not_counted():
    r = reading(flight=flight())
    r.setup_s = 80.0
    before = [m.read(r) for m in READERS]
    r.flight[4]["compile_totals"]["built"] = 7  # step 12: inside the window
    r.flight[4]["wall_s"] = 70.0
    assert [m.read(r) for m in READERS] == before
    # a window that opens one block later takes the row in
    r.cycles = [{"step": 16, "wall_s": 7.0}]
    assert setup_programs_built.read(r) == 90 + 2 + 6 + 7


def test_no_setup_row_reads_nothing():
    r = reading(flight=flight(setup=None))  # the parent of the PR that added the row
    r.setup_s = 80.0
    assert [m.read(r) for m in READERS] == [None] * 5
    # a trainer built outside `trlx_tpu.train()` has no `init_s`: the share is not guessed
    bare = {k: v for k, v in SETUP.items() if k != "init_s"}
    r = reading(flight=flight(setup=bare))
    r.setup_s = 80.0
    assert setup_unseen_share.read(r) is None and setup_programs_built.read(r) == 98


def test_unseen_share_is_never_negative():
    r = reading(flight=flight())
    r.setup_s = 60.0  # less than the parts the program saw: clocks that disagree
    assert setup_unseen_share.read(r) == 0.0
