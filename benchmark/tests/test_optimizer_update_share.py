"""`optimizer_update_share` on a hand-made `Reading`: the scope's device seconds
over the busy seconds, and None where there is nothing to read."""

import pytest

from benchmark.layer_metrics import optimizer_update_share
from benchmark.tests.test_layer_readers import reading


def test_the_scopes_seconds_over_busy_seconds():
    trace = {
        "busy_s": 4.0,
        "scopes_by_self_time": [
            ["jit(fused_train_step)/while/body/closed_call/optimizer_update/dynamic_update_slice", 0.25],
            ["jit(fused_train_step)/while/body/closed_call/optimizer_update/while/body/sqrt", 0.5],
            ["jit(train_step)/optimizer_update", 0.05],
            ["jit(fused_train_step)/while/body/closed_call/loss/logprobs", 1.0],  # not under the scope
            ["jit(fused_train_step)/optimizer_update_v2", 9.0],  # another name
            ["", 0.2]],
    }
    assert optimizer_update_share.read(reading(trace=trace)) == pytest.approx(100 * 0.8 / 4.0)


def test_nothing_to_read_is_nothing_never_a_share_of_0():
    other = {"busy_s": 4.0, "scopes_by_self_time": [["jit(generate)/while/body/decode_step", 1.0]]}
    assert optimizer_update_share.read(reading(trace=other)) is None  # a program without the scope
    assert optimizer_update_share.read(reading()) is None  # an untraced run
