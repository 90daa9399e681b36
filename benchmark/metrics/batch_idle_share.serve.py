"""Share of the window in which the device was idle under the program's
`serve.batch` spans: the engine's host work with a batch in hand
(benchmark/progspans.py)."""

from progspans import idle_share_under


def read(outcome, reduced, ctx):
    return idle_share_under(reduced, ["serve.batch"])
