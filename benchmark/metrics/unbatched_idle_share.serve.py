"""Share of the window in which the device was idle under a
`serve.request` span and under no `serve.batch` span: the gathering
wait, HTTP admission and response assembly (benchmark/progspans.py).
The rest of the idle time is the device with no request in the server."""

from progspans import idle_share_under


def read(outcome, reduced, ctx):
    return idle_share_under(reduced, ["serve.request"], outside=["serve.batch"])
