"""The host-side work model: token counts by hand, and a cell by name."""
import numpy as np
import pytest

from bench import workmodel
from bench.traffic.open_loop import Schedule

# one request due at once: a 32-token prompt (two 16-token chunks) and
# 3 output tokens.  Chunk calls end at 0.1 and 0.2 s (the second gives
# the first token); decode steps of 3 pages at 0.01 s a page end at 0.23
# and 0.26 s.
ONE = Schedule(due_s=np.zeros(1), prompt_len=np.array([32]),
               output_len=np.array([3]))


@pytest.mark.parametrize("lead_s,seconds,want", [
    (0.0, 1.0, 3.0),        # all three tokens
    (0.0, 0.25, 8.0),       # 0.2 and 0.23 s: two tokens in 0.25 s
    (0.21, 1.0, 2.0),       # the decode tokens only
])
def test_tokens_by_hand(lead_s, seconds, want):
    got = workmodel.tokens_per_s(
        ONE, rows=2, chunk=16, block=16, heads=1, layers=1, lead_s=lead_s,
        seconds=seconds, chunk_ms=100.0, step_ms=0.0, page_us=1e4)
    assert got == pytest.approx(want)


def test_cell_by_name(capsys):
    assert workmodel.main(["--workload", "internlm2-20b.code",
                           "--seeds", "1,2,2", "--seconds", "10"]) == 0
    lines = capsys.readouterr().out.splitlines()
    assert [line.split()[0] for line in lines[:3]] == ["1", "2", "2"]
    # the same seed gives the same work
    assert lines[1].split()[1] == lines[2].split()[1]
    assert lines[3].startswith("median ")
