"""Median duration of the program's `solve.prepare` spans that start
inside the window: the per-call trace, lower, and compile or cache load
of a solve (benchmark/progspans.py)."""

from progspans import median_ms_starting_in_window


def read(outcome, reduced, ctx):
    return median_ms_starting_in_window(reduced, "solve.prepare")
