"""Median of the batcher's queue wait (Server-Timing `queue`) over the
window's answered requests."""

import statistics


def read(outcome, reduced, ctx):
    waits = [a.timing["queue"] for a in outcome.answers
             if a.ok and "queue" in a.timing]
    return statistics.median(waits) if waits else None
