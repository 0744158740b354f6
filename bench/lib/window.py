"""The window rule: whole rounds only.

One client, closed loop: rounds of the cell's queries run back to back, and
the window ends at the first round boundary at or after `seconds`. Every run
of a cell therefore holds whole rounds and the same mix of queries, and a rate
is taken over all the work and all the time of the window.
"""

from __future__ import annotations

import time


def run_window(one_round, seconds: float, clock=time.perf_counter) -> dict:
    """Call `one_round(i)` (which returns the queries it completed) until
    `seconds` have passed at a round boundary; at least one round runs."""
    start = clock()
    rounds = completed = 0
    while True:
        completed += one_round(rounds)
        rounds += 1
        elapsed = clock() - start
        if elapsed >= seconds:
            return {"rounds": rounds, "completed": completed, "seconds": elapsed}
