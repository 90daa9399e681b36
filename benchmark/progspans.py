"""Device idle time put down to the program's own spans.

The program (wavetpu/obs/tracing.py) opens a profiler annotation named
by each span's kind (`solve.prepare`, `serve.batch`, ...) whenever the
profiler runs, so the spans sit on the trace's host plane beside the
device ops, on one clock.  An annotation that carries metadata is named
`kind#k=v,...#`; a span is matched on the part before `#`.

"Idle under S": the device's idle intervals inside the window (the host
span `bench.window`), intersected with the union of every host span
named S on any thread, averaged over the cell's devices, as a share of
the window.  A trace with no span of a kind at all (a program that has
not got it) reads None, so the result line leaves the metric out; a
kind that is in the trace but over none of the window's idle time reads
0.
"""

from __future__ import annotations

import statistics
from typing import Iterable, List, Optional, Sequence

from tracereduce import idle_gaps, merged, overlap_length, union_length


def kind(name: str) -> str:
    return name.split("#", 1)[0]


def intervals(reduced, kinds: Iterable[str]) -> List[list]:
    want = set(kinds)
    return [[s.start, s.end] for s in reduced.spans if kind(s.name) in want]


def intersect(a, b) -> List[list]:
    """The intervals of (union of a) intersected with (union of b)."""
    a, b = merged(a), merged(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append([lo, hi])
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def idle_share_under(reduced, under: Sequence[str],
                     outside: Sequence[str] = ()) -> Optional[float]:
    """Percent of the window in which the device was idle while a span
    named in `under` was open and none named in `outside` was."""
    spans = intervals(reduced, under)
    if not spans or not reduced.ops or reduced.window_s <= 0:
        return None
    excluded = intervals(reduced, outside)
    total = 0.0
    for ops in reduced.ops.values():
        gaps = idle_gaps([(o.start, o.end) for o in ops], reduced.window)
        hit = intersect(gaps, spans)
        total += union_length(hit) - overlap_length(hit, excluded)
    return 100.0 * total / len(reduced.ops) / reduced.window_s


def median_ms_starting_in_window(reduced, name: str) -> Optional[float]:
    """Median duration, in ms, of the spans named `name` that start
    inside the window."""
    lo, hi = reduced.window
    durs = [s.end - s.start for s in reduced.spans
            if kind(s.name) == name and lo <= s.start < hi]
    return 1e3 * statistics.median(durs) if durs else None
