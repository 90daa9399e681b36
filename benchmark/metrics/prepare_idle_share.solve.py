"""Share of the window in which the device was idle under the program's
`solve.prepare` spans: each solve's trace, lower, and XLA compile or
persistent-cache load (benchmark/progspans.py)."""

from progspans import idle_share_under


def read(outcome, reduced, ctx):
    return idle_share_under(reduced, ["solve.prepare"])
