"""Tail arithmetic on raw client stamps."""
import math
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))

from stats import nearest_rank, token_gaps, ttfts  # noqa: E402


class Rec:
    def __init__(self, due, stamps, submitted=None):
        self.due, self.stamps = due, stamps
        self.submitted = due if submitted is None else submitted


def test_nearest_rank_uses_every_sample():
    vals = list(range(1, 101))  # 1..100
    assert nearest_rank(vals, 50) == 50
    assert nearest_rank(vals, 90) == 90
    assert nearest_rank(vals, 95) == 95
    assert nearest_rank([7.0], 90) == 7.0
    assert nearest_rank(list(reversed(vals)), 90) == 90


def test_missing_request_counts_against_the_tail():
    recs = [Rec(0.0, [0.1 * (i + 1)]) for i in range(9)] + [Rec(0.0, [])]
    t = ttfts(recs)
    assert math.isinf(t[-1])
    assert math.isinf(nearest_rank(t, 95))
    assert nearest_rank(t, 50) == 0.5


def test_stall_in_the_window_moves_the_tail():
    """A stall delays every request due behind it: timed from the due time
    the tail sees it, timed from the late submission it would not."""
    steady = [Rec(float(i), [i + 0.2]) for i in range(20)]
    # requests 10..13 were due during a 3 s stall: sent late, served fast
    stalled = [Rec(float(i), [13.0 + 0.2 + 0.01 * (i - 10)] if 10 <= i < 14
                   else [i + 0.2], submitted=13.0 if 10 <= i < 14 else None)
               for i in range(20)]
    assert abs(nearest_rank(ttfts(steady), 90) - 0.2) < 1e-9
    assert nearest_rank(ttfts(stalled), 90) > 1.0
    from_submit = [r.stamps[0] - r.submitted for r in stalled]
    assert nearest_rank(from_submit, 90) < 0.3


def test_token_gaps_are_all_gaps_of_all_requests():
    recs = [Rec(0.0, [1.0, 1.5, 3.0]), Rec(0.0, [2.0]), Rec(0.0, [4.0, 4.25])]
    assert sorted(token_gaps(recs)) == [0.25, 0.5, 1.5]
