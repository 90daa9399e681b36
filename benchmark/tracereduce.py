"""From a profiler trace of one window to the per-layer numbers.

The trace is the `.xplane.pb` that `jax.profiler.start_trace` writes.
Its device planes (`/device:TPU:<id>`) hold one event per executed
operation on the line `XLA Ops`; its host plane holds the annotations
open on each thread (the benchmark's `bench.*` spans, the program's
`serve.*` spans).  The window is the host span `bench.window`.

- busy: the union of the operation intervals of a device inside the
  window; idle share is 1 - busy / window, averaged over the devices.
- an op's name in the trace is its HLO instruction text
  (`%custom-call.3 = (f32[..], ..) custom-call(f32[..] %a, ..), ..`);
  its short name is the part before ` = `.
- kernels: operations that are Mosaic custom calls (`tpu_custom_call`
  in the op's text), found by kind since the kernels carry no names.
- a kernel's bytes: the sizes of its results and of its distinct
  operands that live in HBM, read from the op's own text in the trace
  (a Pallas call lists one buffer several times, once for each block
  view; a layout with a memory space `S(n)` is on-chip memory, not
  HBM; the layout constraints after `custom_call_target=` repeat the
  operands): the HBM data the call takes and gives back, the least any
  implementation of that call must move.
- control flow (`while`, `conditional`, `call`) spans the ops it runs:
  it counts towards busy time and is left out of the op breakdown.
- collectives: operations named for a collective; their exposed part is
  the time one runs while no other operation runs on that device.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|ppermute|psum|send|recv"
)
ARRAY = re.compile(r"\b(pred|s8|u8|s16|u16|f16|bf16|s32|u32|f32|s64|u64|f64)"
                   r"\[([0-9,]*)\](\{[^{}]*\})?(?:\s+(%[\w.\-]+))?")
OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")
CONTROL_FLOW = ("while", "conditional", "call")
ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2,
            "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
            "f64": 8}
HOST_FRAME = "$"  # the Python tracer's frames, not annotations


@dataclass
class Op:
    name: str
    start: float  # seconds, trace clock
    end: float
    kernel: bool
    collective: bool
    control: bool  # while/conditional/call: spans other ops
    bytes: Optional[float]  # operands + results, kernels only


@dataclass
class Span:
    name: str
    start: float
    end: float


@dataclass
class Reduced:
    window: Tuple[float, float]
    ops: Dict[int, List[Op]]          # device id -> ops in the window
    spans: List[Span] = field(default_factory=list)
    peak_gbps: Optional[float] = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_by_device(self) -> Dict[int, float]:
        return {d: union_length([(o.start, o.end) for o in ops])
                for d, ops in self.ops.items()}

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        b = self.busy_by_device()
        return sum(b.values()) / len(b) if b else 0.0

    def idle_share(self) -> Optional[float]:
        if not self.ops or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def kernel_ops(self) -> List[Op]:
        return [o for ops in self.ops.values() for o in ops if o.kernel]

    def kernel_busy_share(self) -> Optional[float]:
        busy = sum(self.busy_by_device().values())
        kernel = sum(o.end - o.start for o in self.kernel_ops())
        return 100.0 * kernel / busy if busy > 0 and kernel > 0 else None

    def kernel_roofline_share(self) -> Optional[float]:
        """Interface bytes of every kernel call over its device time, as
        a share of the chip's HBM bandwidth."""
        ks = [o for o in self.kernel_ops() if o.bytes]
        t = sum(o.end - o.start for o in ks)
        if not ks or t <= 0 or not self.peak_gbps:
            return None
        return 100.0 * sum(o.bytes for o in ks) / t / (self.peak_gbps * 1e9)

    def collective_exposed_share(self) -> Optional[float]:
        shares = []
        for ops in self.ops.values():
            coll = [(o.start, o.end) for o in ops if o.collective]
            if not coll:
                continue
            other = [(o.start, o.end) for o in ops if not o.collective]
            exposed = union_length(coll) - overlap_length(coll, other)
            shares.append(exposed / self.window_s)
        return 100.0 * sum(shares) / len(shares) if shares else None

    def breakdown(self, top: int = 10) -> dict:
        by_name: Dict[str, float] = {}
        for ops in self.ops.values():
            for o in ops:
                if not o.control:
                    by_name[o.name] = by_name.get(o.name, 0.0) + (o.end - o.start)
        n_dev = max(len(self.ops), 1)
        device_ops = sorted(
            ([k, v / n_dev] for k, v in by_name.items()), key=lambda kv: -kv[1]
        )[:top]
        gaps = []
        for ops in self.ops.values():
            for lo, hi in idle_gaps([(o.start, o.end) for o in ops], self.window):
                gaps.append([self.host_label(lo, hi), hi - lo])
        gaps.sort(key=lambda g: -g[1])
        return {"device_ops": device_ops, "idle_gaps": gaps[:top]}

    def host_label(self, lo: float, hi: float) -> str:
        """The innermost host span open over the middle of [lo, hi]."""
        mid = 0.5 * (lo + hi)
        open_ = [s for s in self.spans
                 if s.start <= mid <= s.end and s.name != WINDOW_SPAN]
        if not open_:
            return "no host span"
        return max(open_, key=lambda s: s.start).name


def merged(intervals):
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return out


def union_length(intervals) -> float:
    return sum(hi - lo for lo, hi in merged(intervals))


def overlap_length(a, b) -> float:
    """Length of (union of a) intersected with (union of b)."""
    a, b = merged(a), merged(b)
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_gaps(intervals, window):
    lo, hi = window
    gaps, t = [], lo
    for a, b in merged(intervals):
        if a > t:
            gaps.append((t, min(a, hi)))
        t = max(t, b)
    if t < hi:
        gaps.append((t, hi))
    return [g for g in gaps if g[1] > g[0]]


def short_name(text: str) -> str:
    return text.split(" = ", 1)[0]


def opcode(text: str) -> str:
    m = OPCODE.search(text.split(" = ", 1)[-1])
    return m.group(1) if m else ""


def interface_bytes(text: str) -> Optional[int]:
    """HBM bytes a custom call takes and gives back: its results and its
    distinct operands, leaving out arrays in on-chip memory."""
    head = text.split(", custom_call_target=", 1)[0]
    result, _, operands = head.split(" = ", 1)[-1].partition(" custom-call(")
    total, seen = 0, set()
    for part, named in ((result, False), (operands, True)):
        for dtype, dims, layout, name in ARRAY.findall(part):
            if "S(" in layout or (named and name in seen):
                continue
            seen.add(name)
            n = 1
            for d in filter(None, dims.split(",")):
                n *= int(d)
            total += n * ITEMSIZE[dtype]
    return total or None


def _stats(event) -> Dict[str, object]:
    try:
        return {k: v for k, v in event.stats}
    except (TypeError, ValueError):
        return {}


def is_kernel(text: str) -> bool:
    return opcode(text) == "custom-call" and "tpu_custom_call" in text


def xplane_path(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {trace_dir}")
    return paths[0]


def reduce_profile(profile, device_ids=None, peak_gbps=None) -> Reduced:
    """Reduce a `jax.profiler.ProfileData` (or anything shaped like it:
    planes with lines with events carrying name, start_ns, duration_ns
    and stats) to the window's device ops and host spans."""
    spans, dev_events = [], {}
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                dev_events.setdefault(int(m.group(1)), []).extend(line.events)
            elif plane.name.startswith("/host:"):
                spans.extend(
                    Span(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if not e.name.startswith(HOST_FRAME)
                )
    windows = [s for s in spans if s.name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} '{WINDOW_SPAN}' spans in the trace")
    w = (windows[0].start, windows[0].end)
    ops = {}
    for dev, events in dev_events.items():
        if device_ids is not None and dev not in device_ids:
            continue
        kept = []
        for e in events:
            lo = max(e.start_ns * 1e-9, w[0])
            hi = min((e.start_ns + e.duration_ns) * 1e-9, w[1])
            if hi <= lo:
                continue
            name, code = short_name(e.name), opcode(e.name)
            kernel = is_kernel(e.name)
            # A kernel cut by the window's edge keeps its share of bytes.
            full = e.duration_ns * 1e-9
            nbytes = interface_bytes(e.name) if kernel else None
            if nbytes and hi - lo < full:
                nbytes = nbytes * (hi - lo) / full
            kept.append(Op(name, lo, hi, kernel, bool(COLLECTIVE.search(code)),
                           code in CONTROL_FLOW, nbytes))
        ops[dev] = kept
    return Reduced(w, ops, spans, peak_gbps)


def profile_options():
    """Annotations only: the Python tracer would record every frame,
    swell the trace and slow the host it measures; no HLO protos."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def reduce_trace(trace_dir: str, devices, peaks) -> Reduced:
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(xplane_path(trace_dir))
    return reduce_profile(profile, {d.id for d in devices}, peaks["hbm_gbps"])


def describe(trace_dir: str, per_line: int = 3) -> str:
    """A by-hand look at a trace: every plane and line, with the first
    events of each and their stats (used before writing code against a
    trace; see PERF.md)."""
    from jax.profiler import ProfileData

    profile = ProfileData.from_file(xplane_path(trace_dir))
    out = []
    for plane in profile.planes:
        lines = list(plane.lines)
        out.append(f"PLANE {plane.name} ({len(lines)} lines)")
        for line in lines:
            events = list(line.events)
            out.append(f"  LINE {line.name} ({len(events)} events)")
            for e in events[:per_line]:
                out.append(f"    {e.name!r} start_ns={e.start_ns} "
                           f"dur_ns={e.duration_ns} stats={_stats(e)!r}"[:2000])
    return "\n".join(out)
