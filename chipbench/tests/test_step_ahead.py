"""``step_ahead_share.answer`` against counters of its own, every expected value computed by hand."""

import types

import pytest

from chipbench import run

NAME = "step_ahead_share.answer"


def ctx_of(before: dict, after: dict):
    return types.SimpleNamespace(
        config={"hidden_size": 64}, window={"latency_ms": [1.0], "end_s": 1.0, "attempted": 1},
        before={"pad": before, "calls": {}}, after={"pad": after, "calls": {"decoder.step": 200}},
        trace={"seconds": {}, "launches": {}}, device_kind="TPU v5 lite",
    )


def test_the_share_is_the_windows_steps_launched_ahead():
    # set-up launched 10 ahead and 4 alone; the window 188 more ahead and 12 more alone: 188 of 200
    ctx = ctx_of({"decoder.step.ahead": [10, 4, 0, 0]}, {"decoder.step.ahead": [198, 16, 0, 0]})
    assert run.load_metric(NAME).read(ctx) == pytest.approx(94.0)
    # a window that began with its counter at zero
    assert run.load_metric(NAME).read(ctx_of({}, {"decoder.step.ahead": [0, 3, 0, 0]})) == 0.0


@pytest.mark.parametrize("after", [{"decoder.step": [30, 10, 45_000, 77_880]}, {"decoder.step.ahead": [7, 2, 0, 0]}])
def test_nothing_is_read_from_a_program_without_the_counter_or_a_window_without_a_step(after):
    """A parent's counters (no ``decoder.step.ahead``), and one whose window launched no step."""
    before = {"decoder.step.ahead": [7, 2, 0, 0]} if "decoder.step.ahead" in after else {}
    assert run.load_metric(NAME).read(ctx_of(before, after)) is None
