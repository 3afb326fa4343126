"""Cell rerank.title.closed rehearsed at a tiny size on the CPU: a well-formed last
line, with and without the trace."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from rehearse import check_line, load_cell, run_cell  # noqa: E402


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_prints_a_well_formed_line(capsys, trace):
    rc, out, err = run_cell(capsys, "rerank.title.closed", trace)
    assert rc == 0
    check_line(load_cell("rerank.title.closed"), out, err, trace)
