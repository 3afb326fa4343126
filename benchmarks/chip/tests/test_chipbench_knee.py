"""The knee rule of ``knee.py``: the highest swept rate that, with every
lower rate, kept pace (no failure, a backlog at the close of at most one
top-rung batch)."""
import importlib.util
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parents[1]


def _knee():
    s = importlib.util.spec_from_file_location("chipbench_knee",
                                               HERE / "knee.py")
    mod = importlib.util.module_from_spec(s)
    s.loader.exec_module(mod)
    return mod.knee


def _lv(rate, backlog, failed=0):
    return {"offered_qps": rate, "backlog_at_close": backlog,
            "failed": failed}


@pytest.mark.parametrize("levels, want", [
    ([_lv(2, 3), _lv(3, 10), _lv(4, 32), _lv(5, 61)], 4),
    ([_lv(5, 61), _lv(2, 3), _lv(3, 40)], 2),          # any order
    ([_lv(2, 3), _lv(3, 40), _lv(4, 8)], 2),           # a later lucky level
    ([_lv(2, 3), _lv(3, 0, failed=1)], 2),             # a failure
    ([_lv(2, 33)], None),                              # none kept pace
])
def test_the_knee_is_the_highest_rate_that_kept_pace(levels, want):
    assert _knee()(levels, 32) == want
