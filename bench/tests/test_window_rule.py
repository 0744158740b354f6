"""The window holds whole rounds and ends at the first boundary at or after
--seconds."""

import pytest

from lib.window import run_window


class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@pytest.mark.parametrize("round_s,seconds,rounds", [
    (3.0, 10.0, 4),      # 3, 6, 9 are short of 10; the fourth round ends at 12
    (40.0, 10.0, 1),     # a round longer than the window: one whole round
    (5.0, 10.0, 2),      # a boundary exactly at --seconds closes the window
    (1.0, 0.0, 1),       # never fewer than one round
])
def test_whole_rounds(round_s, seconds, rounds):
    clock = Clock()

    def one_round(i):
        clock.t += round_s
        return 2

    w = run_window(one_round, seconds, clock)
    assert w == {"rounds": rounds, "completed": 2 * rounds, "seconds": rounds * round_s}
