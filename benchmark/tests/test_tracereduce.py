"""The reduction from a profiler trace to per-layer numbers, on a
synthetic trace with known answers and on a small trace recorded on
the chip (tests/data/small.xplane.pb: one compensated k=4 solve at
N=128/41 on a v5e, traced whole)."""

import os
from types import SimpleNamespace as NS

import pytest

import tracereduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
CALL = ('%custom-call.1 = (f32[8,128]{1,0:T(8,128)}, bf16[4,128]{1,0}, '
        'f32[4,128]{1,0:T(4,128)S(1)}) custom-call(f32[8,128]{1,0} %p0, '
        'f32[8,128]{1,0} %p0, s32[2]{0} %p1, f32[16]{0:T(128)S(1)} %p2), '
        'custom_call_target="tpu_custom_call", operand_layout_constraints='
        '{f32[8,128]{1,0}, f32[8,128]{1,0}, s32[2]{0}, f32[16]{0}}, '
        'frontend_attributes={kernel_metadata={}}')


def ev(name, start_us, dur_us):
    return NS(name=name, start_ns=start_us * 1000, duration_ns=dur_us * 1000,
              stats=[])


def profile(device_ops, host_spans, devices=(0,)):
    planes = [NS(name="/host:CPU", lines=[NS(name="python3", events=host_spans)])]
    for d in devices:
        planes.append(NS(name=f"/device:TPU:{d}", lines=[
            NS(name="XLA Modules", events=[ev("jit_run", 0, 10_000)]),
            NS(name="XLA Ops", events=device_ops),
        ]))
    return NS(planes=planes)


def test_interface_bytes_counts_results_and_operands():
    # HBM results 8*128*4 + 4*128*2, distinct HBM operands 8*128*4 + 2*4:
    # %p0 is listed twice (two block views of one buffer), the S(1)
    # arrays are in on-chip memory, and the layout constraints repeat
    # the operands
    assert tr.interface_bytes(CALL) == 4096 + 1024 + 4096 + 8
    assert tr.is_kernel(CALL) and not tr.is_kernel("%fusion.1 = f32[] fusion()")
    assert tr.opcode(CALL) == "custom-call"
    assert tr.opcode("%while.4 = (s32[], f32[8]{0:T(128)S(1)}) while((s32[], "
                     "f32[8]) %t), condition=%c, body=%b") == "while"
    assert tr.interface_bytes("no shapes here") is None


def test_synthetic_trace_known_answers():
    # window 100..1100 us; kernel 100..500 and 600..800; fusion 450..550
    # (overlaps the first kernel by 50); all-reduce 900..1000 alone,
    # collective-permute 700..750 hidden under the second kernel.
    ops = [
        ev(CALL, 100, 400),
        ev("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %a), kind=kLoop", 450, 100),
        ev(CALL, 600, 200),
        ev("%collective-permute-done.3 = f32[8]{0} collective-permute-done("
           "(f32[8]{0}, f32[8]{0}) %cp)", 700, 50),
        ev("%all-reduce.4 = f32[] all-reduce(f32[] %x), to_apply=%add", 900, 100),
        ev("%copy.9 = f32[8]{0} copy(f32[8]{0} %y)", 1500, 100),  # outside
        ev("%while.1 = (s32[]) while((s32[]) %t), body=%b", 100, 450),
    ]
    spans = [ev("bench.window", 100, 1000), ev("bench.solve", 100, 900),
             ev("bench.gap", 1000, 50), ev("$solo.py:82 solve", 1000, 50)]
    red = tr.reduce_profile(profile(ops, spans), {0}, peak_gbps=819.0)
    assert red.window_s == pytest.approx(1e-3)
    # busy = 100..550 (450) + 600..800 (200) + 900..1000 (100) = 750 us
    assert red.busy_s == pytest.approx(750e-6)
    assert red.idle_share() == pytest.approx(25.0)
    assert red.kernel_busy_share() == pytest.approx(100.0 * 600 / 750)
    assert not any(o.kernel or o.collective for o in red.ops[0] if o.control)
    # the all-reduce is exposed for 100 us, the permute not at all
    assert red.collective_exposed_share() == pytest.approx(10.0)
    per_call = tr.interface_bytes(CALL)
    want = 100.0 * 2 * per_call / 600e-6 / 819e9
    assert red.kernel_roofline_share() == pytest.approx(want)
    bd = red.breakdown()
    assert bd["device_ops"][0] == ["%custom-call.1", pytest.approx(600e-6)]
    gaps = dict((round(s * 1e6), label) for label, s in bd["idle_gaps"])
    assert gaps == {50: "bench.solve", 100: "bench.gap"}


def test_device_filter_and_average():
    ops = [ev(CALL, 0, 500)]
    spans = [ev("bench.window", 0, 1000)]
    red = tr.reduce_profile(profile(ops, spans, devices=(0, 1)), {0, 1})
    assert sorted(red.ops) == [0, 1]
    assert red.idle_share() == pytest.approx(50.0)
    assert red.kernel_roofline_share() is None  # no peak given
    assert tr.reduce_profile(profile(ops, spans, (0, 1)), {1}).ops.keys() == {1}


def test_nothing_to_read_gives_none():
    red = tr.reduce_profile(profile([ev("%fusion.1 = f32[] fusion()", 0, 10)],
                                    [ev("bench.window", 0, 1000)]), {0}, 819.0)
    assert red.kernel_busy_share() is None
    assert red.kernel_roofline_share() is None
    assert red.collective_exposed_share() is None


def test_window_span_required():
    with pytest.raises(ValueError):
        tr.reduce_profile(profile([], []), {0})


@pytest.mark.skipif(not os.path.exists(os.path.join(DATA, "small.xplane.pb")),
                    reason="no recorded trace")
def test_recorded_chip_trace():
    from jax.profiler import ProfileData

    prof = ProfileData.from_file(os.path.join(DATA, "small.xplane.pb"))
    red = tr.reduce_profile(prof, None, peak_gbps=819.0)
    assert red.ops, "no device plane with XLA Ops"
    kernels = red.kernel_ops()
    assert kernels and all(k.bytes for k in kernels)
    assert 0.0 < red.idle_share() < 100.0
    assert 0.0 < red.kernel_busy_share() <= 100.0
    assert 0.0 < red.kernel_roofline_share() <= 100.0
    assert red.collective_exposed_share() is None  # one chip
    assert red.breakdown()["device_ops"]
