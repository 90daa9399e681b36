"""Share of the window in which the device was idle under the program's
`solve.finish` spans: each solve's result assembly and solve metrics
(benchmark/progspans.py)."""

from progspans import idle_share_under


def read(outcome, reduced, ctx):
    return idle_share_under(reduced, ["solve.finish"])
