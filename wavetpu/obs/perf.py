"""Performance X-ray: roofline attribution + device-memory watermarks.

BENCH rounds pinned the solver family as memory-bandwidth-bound (the
k-step onion exists exactly to cut HBM traffic per layer), yet nothing
in the obs stack said how close a given solve actually ran to that
roofline, and nothing watched HBM pressure at all.  This module closes
both gaps:

ROOFLINE ATTRIBUTION.  `model_bytes_per_cell` is the ONE shared
analytic cost model for every solver path - cells x steps x scheme x
path x k x dtype -> bytes moved per cell-update - reconciled with
`choose_kstep_block` / `choose_kstep_comp_block`'s VMEM accounting (the
onion models read the SAME block depth the chooser blesses, so the
modeled traffic follows the block the kernel actually runs).  From it,
`solve_perf` turns a measured Gcell/s into:

    model_gbps        = bytes_per_cell x achieved Gcell/s  (achieved HBM
                        bandwidth under the model)
    roofline_fraction = model_gbps / peak_gbps             (how close to
                        the memory roofline this solve ran)
    arithmetic_intensity = flops_per_cell / bytes_per_cell

`metrics.record_solve` stamps these on every instrumented solve
(gauges + per-path GB/s histograms), and the serve engine attaches the
same attrs to its `serve.execute` spans.  `peak_gbps` is the chip's
published HBM bandwidth, looked up by `device_kind`; off the TPU there
is no roofline, and the fraction is None ("not measured").

DEVICE-MEMORY OBSERVABILITY.  `memory_snapshot()` reads
`device.memory_stats()` (None on backends without it - e.g. the CPU
backend this repo's CI runs on); `record_memory()` samples it into
gauges around solo solves, per supervisor chunk, and per serve batch,
maintains a process-lifetime high-watermark gauge, counts watermark
raises, and fires a `memory.warn` trace event + counter when bytes in
use cross a configurable threshold (WAVETPU_MEM_WARN_BYTES).  The
"unsupported" verdict is probed once and cached, so on backends without
memory_stats every later call is a dict lookup - the no-op discipline
of PR 5.

`wavetpu profile` (profile_main) brackets one solve - or a whole serve
window - with `jax.profiler.start_trace`/`stop_trace`, so the PR 5 span
annotations (tracing.py opens a matching `jax.profiler.TraceAnnotation`
per span) land INSIDE the device trace, then prints a post-capture
summary.

Metric catalog additions (docs/observability.md is the user copy):

  wavetpu_solve_roofline_fraction{path}   gauge: last solve's fraction
  wavetpu_solve_model_gbps{path}          gauge: last solve's modeled GB/s
  wavetpu_solve_gbps{path}                histogram: modeled-GB/s dist
  wavetpu_device_bytes_in_use{context}    gauge: last sample
  wavetpu_device_peak_bytes{context}      gauge: allocator peak at sample
  wavetpu_device_memory_watermark_bytes   gauge: process-lifetime max
  wavetpu_device_memory_watermark_raises_total  counter: times it rose
  wavetpu_device_memory_warn_total        counter: threshold crossings

jax is NEVER imported at module level (same discipline as tracing.py):
the callers that need the model all run inside jax-using layers, and
`sys.modules` is consulted for the backend-dependent defaults.
"""

from __future__ import annotations

import os
import sys
import threading
import time
from typing import Callable, Dict, Optional, Sequence

from wavetpu.obs import tracing
from wavetpu.obs.registry import MetricsRegistry, get_registry

# Approximate op counts per cell-update, read off the kernel bodies
# (kernels/stencil_pallas.py): the standard step is a 7-point Laplacian
# (3 axes x [2 adds + 1 axpy-style combine] ~ 12) plus the leapfrog
# combine 2u + C*lap - u_prev (~3); the compensated velocity form adds
# the increment accumulate and the Kahan two-sum (~6 more).  These feed
# arithmetic intensity only - the family is bandwidth-bound, so bytes
# are the number that matters and flops just document WHY.
FLOPS_PER_CELL = {"standard": 15.0, "compensated": 21.0}

# Published HBM bandwidth of one chip, keyed by jax's `device_kind`.
# Source: Google Cloud documentation, "TPU v5e" (16 GB of HBM at
# 819 GB/s).  A TPU kind missing here is an error, not a default.
PEAK_GBPS_BY_KIND = {"TPU v5 lite": 819.0}

# Serve-layer dtype names -> state itemsize (the engine's roofline
# call resolves its ProgramKey dtype string through this).
DTYPE_ITEMSIZE = {"f32": 4, "f64": 8, "bf16": 2}


def peak_gbps() -> Optional[float]:
    """The roofline ceiling of device 0: its published HBM GB/s, or None
    off the TPU (and in jax-free processes), where no roofline exists.
    Raises ValueError for a TPU kind the table does not know."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return None
    if dev.device_kind not in PEAK_GBPS_BY_KIND:
        raise ValueError(
            f"no published HBM bandwidth for TPU kind "
            f"{dev.device_kind!r}; add it to PEAK_GBPS_BY_KIND with its "
            f"source"
        )
    return PEAK_GBPS_BY_KIND[dev.device_kind]


def _is_comp_onion(path: str, scheme: str) -> bool:
    return path in ("kfused_comp", "kfused_comp_sharded") or (
        path == "kfused" and scheme == "compensated"
    )


def model_bytes_per_cell(
    path: str,
    *,
    scheme: str = "standard",
    k: int = 1,
    n: Optional[int] = None,
    itemsize: int = 4,
    v_itemsize: Optional[int] = None,
    carry: bool = True,
    with_field: bool = False,
    block_x: Optional[int] = None,
    depth: Optional[int] = None,
    ghosts: bool = False,
) -> Optional[float]:
    """HBM bytes moved per cell-update under the path's traffic model:

     * 1-step paths (`leapfrog`/`roll`/`pallas`/`sharded`, standard
       scheme): 3 state streams (u_prev + u in, u_next out) x itemsize,
       plus one f32 field stream under variable c.
     * 1-step compensated (`compensated`, or `sharded` with
       scheme="compensated"): u/v/carry each in + out = 6 streams.
     * standard k-step onion (`kfused`/`sharded_kfused`): per k-block of
       bx planes the pipeline fetches (bx + 2k) prev + (bx + 2k) cur
       onions and writes 2 bx-plane slabs -> (4bx + 4k) state planes
       per (k x bx) cell-layers; the field onion adds (bx + 2k) f32
       planes.  bx is `block_x` or what `choose_kstep_block` blesses -
       the SAME accounting that sizes the kernel's VMEM pipeline, so
       model and kernel can never drift.  The sharded variants choose
       their block against the SHARD depth with ghost buffers in the
       pipeline (`depth=`/`ghosts=True` - the same arguments the
       solvers pass the chooser); the bytes formula is unchanged (ghost
       planes replace the wraparound halo reads one-for-one), only the
       blessed bx moves.
     * compensated velocity-form onion (`kfused_comp[_sharded]`): u and
       v onions ride in+out at their own itemsizes ((2bx + 2k) planes
       each); the carry rides slab-only (2bx planes) at an effective
       2 B/plane (the calibrated figure from the measured BENCH rows -
       Mosaic keeps part of the carry stream resident); carry-less
       (bf16-increment) mode drops it.  bx from
       `choose_kstep_comp_block`.

    Returns None when the onion does not fit VMEM at this (n, k, dtype)
    per the chooser - the caller then has no roofline model to report,
    which is the honest answer.
    """
    onion = path in ("kfused", "sharded_kfused") and scheme != "compensated"
    comp_onion = _is_comp_onion(path, scheme)
    if not onion and not comp_onion:
        if scheme == "compensated" or path == "compensated":
            return 6.0 * itemsize
        return 3.0 * itemsize + (4.0 if with_field else 0.0)
    if n is None:
        return None
    # Lazy: stencil_pallas imports jax; every caller of an onion model
    # already runs inside a jax-using layer.
    from wavetpu.kernels.stencil_pallas import (
        choose_kstep_block,
        choose_kstep_comp_block,
    )

    if onion:
        bx = block_x or choose_kstep_block(
            n, k, itemsize, depth=depth, ghosts=ghosts,
            field=with_field,
        )
        if bx is None:
            return None
        per_block = float((4 * bx + 4 * k) * itemsize)
        if with_field:
            per_block += (bx + 2 * k) * 4.0
        return per_block / (k * bx)
    v_item = itemsize if v_itemsize is None else v_itemsize
    bx = block_x or choose_kstep_comp_block(
        n, k, itemsize, v_item, itemsize if carry else None,
        depth=depth, ghosts=ghosts, field=with_field,
    )
    if bx is None:
        return None
    per_block = float(
        (2 * bx + 2 * k) * itemsize + (2 * bx + 2 * k) * v_item
    )
    if carry:
        per_block += 2 * bx * 2.0  # calibrated effective carry traffic
    if with_field:
        per_block += (bx + 2 * k) * 4.0
    return per_block / (k * bx)


def flops_per_cell(scheme: str = "standard") -> float:
    return FLOPS_PER_CELL.get(scheme, FLOPS_PER_CELL["standard"])


def solve_perf(
    gcells_per_s: float,
    path: str,
    *,
    scheme: str = "standard",
    k: int = 1,
    n: Optional[int] = None,
    itemsize: int = 4,
    v_itemsize: Optional[int] = None,
    carry: bool = True,
    with_field: bool = False,
    block_x: Optional[int] = None,
    depth: Optional[int] = None,
    ghosts: bool = False,
) -> Optional[Dict[str, float]]:
    """One solve's roofline attribution, or None when no model exists
    for the config (onion over VMEM, zero throughput)."""
    if not gcells_per_s or gcells_per_s <= 0:
        return None
    bpc = model_bytes_per_cell(
        path, scheme=scheme, k=k, n=n, itemsize=itemsize,
        v_itemsize=v_itemsize, carry=carry, with_field=with_field,
        block_x=block_x, depth=depth, ghosts=ghosts,
    )
    if bpc is None:
        return None
    peak = peak_gbps()
    model_gbps = gcells_per_s * bpc
    fpc = flops_per_cell(scheme)
    return {
        "model_bytes_per_cell": round(bpc, 4),
        "model_gbps": round(model_gbps, 3),
        "peak_gbps": peak,
        "roofline_fraction": (
            None if peak is None else round(model_gbps / peak, 4)
        ),
        "flops_per_cell": fpc,
        "arithmetic_intensity": round(fpc / bpc, 4),
    }


_GBPS_BUCKETS = (1.0, 5.0, 10.0, 25.0, 50.0, 100.0, 150.0, 200.0,
                 250.0, 350.0, 500.0, 1000.0)


def record_roofline(registry: Optional[MetricsRegistry], path: str,
                    perf: Optional[Dict[str, float]]
                    ) -> Optional[Dict[str, float]]:
    """Stamp one solve's roofline attribution into `registry` (the
    process registry by default).  Returns `perf` unchanged so call
    sites can also attach the attrs to an open span."""
    if perf is None:
        return None
    reg = registry if registry is not None else get_registry()
    if perf["roofline_fraction"] is not None:
        reg.gauge(
            "wavetpu_solve_roofline_fraction",
            "modeled-GB/s share of the memory roofline, most recent "
            "solve", ("path",),
        ).set(perf["roofline_fraction"], path=path)
    reg.gauge(
        "wavetpu_solve_model_gbps",
        "achieved HBM GB/s under the path's traffic model, most recent "
        "solve", ("path",),
    ).set(perf["model_gbps"], path=path)
    reg.histogram(
        "wavetpu_solve_gbps",
        "per-solve modeled-GB/s distribution", ("path",),
        buckets=_GBPS_BUCKETS,
    ).observe(perf["model_gbps"], path=path)
    return perf


# ------------------------------------------------- device memory


_mem_lock = threading.Lock()
# None = not yet probed; False = backend has no memory_stats (every
# later call short-circuits); True = supported.
_mem_supported: Optional[bool] = None
# Test hook: a callable returning a memory_stats-shaped dict (or None)
# instead of reading the real device.
_stats_provider: Optional[Callable[[], Optional[dict]]] = None
_warn_bytes_override: Optional[int] = None


def set_memory_stats_provider(
    fn: Optional[Callable[[], Optional[dict]]]
) -> None:
    """Test hook: replace the device read (None restores it and resets
    the cached supported/unsupported verdict)."""
    global _stats_provider, _mem_supported
    with _mem_lock:
        _stats_provider = fn
        _mem_supported = None


def configure_memory_warn(warn_bytes: Optional[int]) -> None:
    """Set (or clear) the warn threshold programmatically; the
    WAVETPU_MEM_WARN_BYTES env var is the CLI-facing knob."""
    global _warn_bytes_override
    _warn_bytes_override = warn_bytes


def memory_warn_bytes() -> Optional[int]:
    if _warn_bytes_override is not None:
        return _warn_bytes_override
    env = os.environ.get("WAVETPU_MEM_WARN_BYTES")
    if env:
        try:
            v = int(float(env))
            if v > 0:
                return v
        except ValueError:
            pass
    return None


def memory_snapshot() -> Optional[Dict[str, int]]:
    """{bytes_in_use, peak_bytes} from device 0's allocator, or None on
    backends without `memory_stats()` (the CPU backend returns None).
    The unsupported verdict is cached - later calls cost a dict lookup."""
    global _mem_supported
    if _mem_supported is False:
        return None
    stats = None
    provider = _stats_provider
    if provider is not None:
        try:
            stats = provider()
        except Exception:
            return None  # transient: no verdict, re-probe next call
    else:
        jax = sys.modules.get("jax")
        if jax is None:
            return None  # backend not up yet: not a verdict, re-probe
        try:
            stats = jax.devices()[0].memory_stats()
        except Exception:
            # A transient read failure (e.g. a race during backend
            # bring-up) is NOT an "unsupported" verdict - do not latch,
            # just skip this sample and re-probe next time.
            return None
    if not stats:
        # memory_stats() answered cleanly with nothing: the backend
        # genuinely has no stats (the CPU backend) - cache that.
        with _mem_lock:
            _mem_supported = False
        return None
    with _mem_lock:
        _mem_supported = True
    in_use = int(stats.get("bytes_in_use", 0))
    return {
        "bytes_in_use": in_use,
        "peak_bytes": int(stats.get("peak_bytes_in_use", in_use)),
    }


def record_memory(registry: Optional[MetricsRegistry] = None,
                  context: str = "solve") -> Optional[Dict[str, int]]:
    """Sample device memory into gauges (labeled by where the sample was
    taken: solve / supervisor / serve), raise the process high-watermark
    gauge when exceeded (counting each raise), and fire the configurable
    warn-threshold event.  No-op (None) on backends without
    memory_stats."""
    snap = memory_snapshot()
    if snap is None:
        return None
    reg = registry if registry is not None else get_registry()
    reg.gauge(
        "wavetpu_device_bytes_in_use",
        "device-allocator bytes in use at the last sample", ("context",),
    ).set(snap["bytes_in_use"], context=context)
    reg.gauge(
        "wavetpu_device_peak_bytes",
        "device-allocator peak bytes at the last sample", ("context",),
    ).set(snap["peak_bytes"], context=context)
    wm = reg.gauge(
        "wavetpu_device_memory_watermark_bytes",
        "highest device bytes-in-use observed this process",
    )
    with reg.lock:
        if snap["bytes_in_use"] > wm.value():
            wm.set(snap["bytes_in_use"])
            reg.counter(
                "wavetpu_device_memory_watermark_raises_total",
                "times the high watermark rose",
            ).inc()
    warn = memory_warn_bytes()
    if warn is not None and snap["bytes_in_use"] > warn:
        reg.counter(
            "wavetpu_device_memory_warn_total",
            "samples above the WAVETPU_MEM_WARN_BYTES threshold",
        ).inc()
        tracing.event(
            "memory.warn", context=context,
            bytes_in_use=snap["bytes_in_use"], warn_bytes=warn,
        )
    return snap


# ------------------------------------------------- `wavetpu profile`


_PROFILE_USAGE = (
    "usage: wavetpu profile --out DIR [--] ARGS...\n"
    "  ARGS is a full wavetpu command line: solver positionals + flags\n"
    "  for one solve, or `serve ...` to profile a whole serve window\n"
    "  (the capture ends when the server shuts down).  The run gets a\n"
    "  --telemetry-dir under DIR unless ARGS already carries one, so\n"
    "  the span annotations land inside the device trace."
)


def _dir_file_summary(root: str) -> Sequence[str]:
    lines = []
    for dirpath, _dirs, files in os.walk(root):
        for f in sorted(files):
            p = os.path.join(dirpath, f)
            try:
                size = os.path.getsize(p)
            except OSError:
                continue
            lines.append(f"  {os.path.relpath(p, root)}  {size} B")
    return lines


def profile_main(argv: Sequence[str]) -> int:
    """`wavetpu profile`: bracket one solve (or serve window) with
    `jax.profiler` so application spans land in a device trace, then
    print a post-capture summary (span stats + captured files).  Do not
    combine with the inner `--profile` flag - this subcommand IS the
    bracket."""
    argv = list(argv)
    out = None
    inner = []
    i = 0
    while i < len(argv):
        a = argv[i]
        if a == "--out" and i + 1 < len(argv):
            out = argv[i + 1]
            i += 2
        elif a.startswith("--out="):
            out = a.split("=", 1)[1]
            i += 1
        elif a == "--":
            inner = argv[i + 1:]
            i = len(argv)
        else:
            inner = argv[i:]
            i = len(argv)
    if not out or not inner:
        print(_PROFILE_USAGE, file=sys.stderr)
        return 2
    if "--profile" in inner or any(
        a.startswith("--profile=") for a in inner
    ):
        print("error: do not pass --profile under `wavetpu profile` "
              "(the subcommand owns the bracket)", file=sys.stderr)
        return 2
    telemetry_dir = None
    for j, a in enumerate(inner):
        if a == "--telemetry-dir" and j + 1 < len(inner):
            telemetry_dir = inner[j + 1]
        elif a.startswith("--telemetry-dir="):
            telemetry_dir = a.split("=", 1)[1]
    if telemetry_dir is None:
        telemetry_dir = os.path.join(out, "telemetry")
        inner = inner + ["--telemetry-dir", telemetry_dir]
    os.makedirs(out, exist_ok=True)

    import jax

    from wavetpu import cli as wavetpu_cli

    print(f"profiling `wavetpu {' '.join(inner)}` -> {out}")
    t0 = time.perf_counter()
    jax.profiler.start_trace(out)
    try:
        rc = wavetpu_cli.main(inner)
    finally:
        jax.profiler.stop_trace()
    wall = time.perf_counter() - t0

    print(f"\nprofile capture: {wall:.3f}s wall, exit {rc}")
    trace_path = os.path.join(telemetry_dir, "trace.jsonl")
    if os.path.exists(trace_path):
        from wavetpu.obs import report as obs_report

        records = obs_report.load_trace(trace_path)
        print("span summary (these kinds are annotated inside the "
              "device trace):")
        print(obs_report.format_summary(obs_report.summarize(records)))
    files = _dir_file_summary(out)
    print(f"captured files under {out}:")
    for line in files[:40]:
        print(line)
    if len(files) > 40:
        print(f"  ... {len(files) - 40} more")
    print("open in xprof/TensorBoard: "
          f"tensorboard --logdir {out}")
    return rc
