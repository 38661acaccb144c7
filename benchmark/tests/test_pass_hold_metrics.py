"""The two readers of the scheduler's hold counters (PR 40). Run by hand
with the rest of ``benchmark/tests``."""

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

from benchmark import prom, run  # noqa: E402

PARENT = "tpu_model_generated_tokens_total 5\n"     # no such counter


def scrape(family, label, counts):
    return prom.parse(PARENT + "".join(
        f'{family}{{{label}="{k}"}} {v}\n' for k, v in counts.items()))


@pytest.mark.parametrize("name,family,label,before,after,want", [
    ("pass_filled_share", "tpu_model_pass_holds_total", "end",
     None, None, None),
    ("pass_filled_share", "tpu_model_pass_holds_total", "end",
     {"filled": 3, "deadline": 1, "none": 9},
     {"filled": 3, "deadline": 1, "none": 40}, None),    # no pass was held
    ("pass_filled_share", "tpu_model_pass_holds_total", "end",
     {"filled": 3, "deadline": 1, "none": 9},
     {"filled": 63, "deadline": 21, "none": 40}, 75.0),
    ("late_launch_share", "tpu_model_decode_launches_total", "timing",
     None, None, None),
    ("late_launch_share", "tpu_model_decode_launches_total", "timing",
     {"ahead": 0, "late": 0, "empty": 2},
     {"ahead": 0, "late": 0, "empty": 90}, None),        # a paged cell
    ("late_launch_share", "tpu_model_decode_launches_total", "timing",
     {"ahead": 10, "late": 0, "empty": 1},
     {"ahead": 107, "late": 3, "empty": 1}, 3.0)])
def test_a_hold_reader_reads_the_windows_counts(name, family, label, before,
                                                after, want):
    """The window's counts alone; nothing, and no raise, on a program
    without the counter (the parent) or with nothing counted."""
    ctx = types.SimpleNamespace(
        before=scrape(family, label, before or {}),
        after=scrape(family, label, after or {}))
    got = run.layer_reader(name).read(ctx)
    assert got == (None if want is None else pytest.approx(want))
