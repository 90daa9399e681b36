"""The readers of the program's spans (benchmark/progspans.py and the
metrics that use it) on fabricated reductions with known answers."""

import pytest

import run
import tracereduce as tr


def reduced(window, busy, spans, devices=(0,)):
    ops = {d: [tr.Op("%fusion.1", lo, hi, False, False, False, None)
               for lo, hi in busy] for d in devices}
    return tr.Reduced(window, ops,
                      [tr.Span(name, lo, hi) for name, lo, hi in spans])


def read(metric, red):
    return run.load_module("metrics", metric).read(None, red, None)


def test_worked_check_serve():
    # window [0, 10] s, device ops [0, 2] and [5, 10]; serve.batch [1, 4]
    # and serve.request [0, 6]: idle [2, 4] is under a batch, idle
    # [4, 5] under a request and no batch
    red = reduced((0.0, 10.0), [(0, 2), (5, 10)],
                  [("bench.window", 0, 10), ("serve.batch", 1, 4),
                   ("serve.request", 0, 6)])
    assert read("batch_idle_share.serve", red) == pytest.approx(20.0)
    assert read("unbatched_idle_share.serve", red) == pytest.approx(10.0)
    assert red.idle_share() == pytest.approx(30.0)


def test_solo_spans_split_the_idle_time():
    # idle gaps [0, 1], [3, 6], [9, 10]; prepares [0, 1.5] and [3.5, 6]
    # (and one before the window), run [1.5, 3.4], finish [3.4, 3.5]
    spans = [("bench.window", 0, 10), ("solve.prepare", -2, -1),
             ("solve.prepare", 0, 1.5), ("solve.run", 1.5, 3.4),
             ("solve.finish", 3.4, 3.5), ("solve.prepare", 3.5, 6),
             ("solve.run", 6, 9.5)]
    red = reduced((0.0, 10.0), [(1, 3), (6, 9)], spans)
    assert read("prepare_idle_share.solve", red) == pytest.approx(35.0)
    assert read("finish_idle_share.solve", red) == pytest.approx(1.0)
    # the prepare before the window is left out: median of 1.5 and 2.5 s
    assert read("prepare_ms.solve", red) == pytest.approx(2000.0)
    shares = (read("prepare_idle_share.solve", red)
              + read("finish_idle_share.solve", red))
    assert shares <= red.idle_share() + 1e-9


def test_metadata_in_the_span_name_is_ignored():
    red = reduced((0.0, 10.0), [(0, 5)],
                  [("bench.window", 0, 10),
                   ("solve.prepare#path=kfused,compiled=0#", 5, 8),
                   ("solve.prepare.other", 8, 10)])
    assert read("prepare_idle_share.solve", red) == pytest.approx(30.0)
    assert read("prepare_ms.solve", red) == pytest.approx(3000.0)


def test_spans_on_several_threads_are_one_union():
    # two overlapping batches on two threads count their overlap once
    red = reduced((0.0, 10.0), [], [("bench.window", 0, 10),
                                    ("serve.batch", 0, 4),
                                    ("serve.batch", 2, 6)])
    assert read("batch_idle_share.serve", red) == pytest.approx(60.0)


def test_averaged_over_devices():
    red = reduced((0.0, 10.0), [(0, 5)],
                  [("bench.window", 0, 10), ("serve.batch", 0, 10)],
                  devices=(0, 1))
    red.ops[1] = []  # the second device idle all window
    assert read("batch_idle_share.serve", red) == pytest.approx(75.0)


def test_no_matching_span_in_the_window_reads_zero():
    # the program has the spans, but none over the window's idle time
    red = reduced((0.0, 10.0), [(0, 10)],
                  [("bench.window", 0, 10), ("solve.finish", 4, 5),
                   ("serve.batch", 12, 13), ("serve.request", 11, 14)])
    assert read("finish_idle_share.solve", red) == 0.0
    assert read("batch_idle_share.serve", red) == 0.0
    assert read("unbatched_idle_share.serve", red) == 0.0
    red = reduced((0.0, 10.0), [(0, 2)],
                  [("bench.window", 0, 10), ("solve.finish", 12, 13)])
    assert read("finish_idle_share.solve", red) == 0.0


@pytest.mark.parametrize("metric", [
    "prepare_idle_share.solve", "finish_idle_share.solve",
    "prepare_ms.solve", "batch_idle_share.serve",
    "unbatched_idle_share.serve"])
def test_a_program_without_the_spans_reads_none(metric):
    """A program that opens no such span (one from before they were
    added) gives no number, and the result line leaves the metric out."""
    red = reduced((0.0, 10.0), [(0, 2)],
                  [("bench.window", 0, 10), ("bench.solve", 0, 9),
                   ("lower_sharding_computation", 3, 4)])
    assert read(metric, red) is None
