"""The reader of the engine's in-place cache counter,
``cache_inplace_share.chat``: on a hand-made context, and silent without
its input, as it is for a program whose engine has no such counter."""
import pytest

import harness

STATS = {"decode_steps": 40, "prefill_calls": 4, "peak_concurrency": 9,
         "shed_blocks": 0, "nonfinite_rows": 0}


@pytest.mark.parametrize("inplace,want", [(44, 100.0), (33, 75.0),
                                          (0, 0.0)])
def test_share_of_updates_done_in_place(inplace, want):
    read = harness.metric_reader("cache_inplace_share.chat")
    ctx = {"engine_stats": {**STATS, "cache_updates": 44,
                            "cache_inplace": inplace}}
    assert read(ctx) == pytest.approx(want)


def test_silent_without_the_counters():
    read = harness.metric_reader("cache_inplace_share.chat")
    assert read({}) is None
    assert read({"engine_stats": STATS}) is None
    assert read({"engine_stats": {**STATS, "cache_updates": 0,
                                  "cache_inplace": 0}}) is None
