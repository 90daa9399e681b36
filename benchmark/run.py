"""One run of one benchmark cell, as the driver starts it:

    python3 benchmark/run.py --workload CELL --seed N --seconds S --trace 0|1

A fresh process sets up (imports, warm-up, compiles or cache loads),
measures for `--seconds`, checks what the timed path produced against
the plain reference (benchmark/reference.py), and prints one JSON
result as the last line of stdout.  With `--trace 1` the same window
runs under the profiler and the line carries the cell's per-layer
metrics instead of its end-to-end ones.

Everything a cell is made of is found by name: BENCHMARK.json names
the configuration file and the traffic mix; the mix names its driver
(drivers/<driver>.py); each per-layer metric is metrics/<name>.py; the
limits of the correctness check are limits/<cell>.json.

`--rehearse` runs the same code on the CPU at the configuration's own
`rehearsal` size with interpret-mode kernels: it prints its checks to
stderr and no result line, since a CPU run gives no device number.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# Fixed paths inside the checkout (the compile cache's key holds its
# path, so it must never move between runs of one checkout).
CACHE_DIR = os.path.join(ROOT, ".bench_cache")
JAX_CACHE_DIR = os.path.join(CACHE_DIR, "jax")
TRACE_DIR = os.path.join(CACHE_DIR, "trace")


class BenchError(Exception):
    """A run that cannot produce a result: exit non-zero, print none."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmark/<kind>/<name>.py, loaded by path (names may hold dots)."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.isfile(path):
        raise BenchError(f"no {kind[:-1]} file {path}")
    modname = f"benchmark_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if modname in sys.modules:
        return sys.modules[modname]
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod  # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Cell:
    """One entry of BENCHMARK.json `workloads`, with what it names."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    limits: Dict[str, float]
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    (cfg,) = [c for c in bench["configs"] if c["name"] == w["config"]]
    here = os.path.join(root, "benchmark")
    return Cell(
        name=workload,
        chips=int(w["chips"]),
        config=load_json(os.path.join(root, cfg["file"])),
        traffic=load_json(os.path.join(here, "traffic", w["traffic"] + ".json")),
        limits=load_json(os.path.join(here, "limits", workload + ".json")),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
    )


@dataclass
class Context:
    """What a driver gets: the cell, the run's arguments, the devices."""

    cell: Cell
    seed: int
    rehearse: bool = False
    compiles: Optional["CompileWatch"] = None
    devices: list = field(default_factory=list)
    peaks: Optional[dict] = None

    @property
    def problem_args(self) -> Dict[str, Any]:
        """The configuration's problem, or its rehearsal size."""
        cfg = dict(self.cell.config["problem"])
        if self.rehearse:
            cfg.update(self.cell.config["rehearsal"])
        return cfg


class CompileWatch:
    """Counts the compiles JAX asks of its persistent cache and the ones
    it found there; the difference is what XLA compiled."""

    def __init__(self, jax):
        self.requests = self.hits = 0
        jax.monitoring.register_event_listener(self._event)

    def _event(self, event, **kwargs):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    @property
    def compiled(self) -> int:
        return self.requests - self.hits


def use_checkout_cache() -> None:
    """Pin JAX's persistent compilation cache inside this checkout before
    jax or wavetpu is imported (wavetpu/jaxcache.py takes the env var and
    sets no other directory)."""
    os.makedirs(JAX_CACHE_DIR, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = JAX_CACHE_DIR
    # No libtpu log files (they would go to /tmp/tpu_logs, and a log
    # directory that fills from run to run slowed set-up down).
    os.environ["TPU_LOG_DIR"] = "disabled"


def import_system():
    """The system under test is the wavetpu package beside benchmark/."""
    sys.path.insert(0, ROOT)
    try:
        import wavetpu
    except ImportError as e:
        raise BenchError(f"wavetpu is not in this checkout: {e}") from None
    where = os.path.dirname(os.path.dirname(os.path.abspath(wavetpu.__file__)))
    if where != ROOT:
        raise BenchError(f"imported wavetpu from {where}, not from {ROOT}")
    return wavetpu


def configure_jax(rehearse: bool):
    import jax

    if rehearse:
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        # Persist every program, however quick to compile, so that a
        # second run of the cell finds all of them.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from wavetpu import jaxcache

    jaxcache.configure()
    return jax


def find_devices(jax, chips: int):
    """The cell's chips, refused unless they are TPUs the peak table
    knows; returns (devices, peaks row)."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(
            f"needs a TPU; JAX found {devs[0].platform} ({len(devs)} devices)"
        )
    if len(devs) < chips:
        raise BenchError(f"needs {chips} chips, JAX found {len(devs)}")
    peaks = load_json(os.path.join(HERE, "peaks.json"))["by_device_kind"]
    kind = devs[0].device_kind
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in benchmark/peaks.json")
    return devs[:chips], peaks[kind]


def memory_peak_bytes(devices) -> int:
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)


def judge(checks: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Every compared number finite and within its limit; a number with
    no limit is a fault of the cell's files."""
    missing = set(checks) - set(limits)
    if missing:
        raise BenchError(f"no limit for {sorted(missing)}")
    return all(
        math.isfinite(v) and v <= limits[k] for k, v in checks.items()
    )


def per_layer_metrics(ctx: Context, outcome, reduced) -> Dict[str, dict]:
    out = {}
    for m in ctx.cell.per_layer:
        value = load_module("metrics", m["name"]).read(outcome, reduced, ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearse: bool = False) -> dict:
    """Set up, measure, check; returns the result object (without
    device metrics in a rehearsal)."""
    cell = load_cell(workload)
    if not rehearse:
        use_checkout_cache()
    import_system()
    jax = configure_jax(rehearse)
    ctx = Context(cell, seed, rehearse)
    ctx.compiles = CompileWatch(jax)
    if rehearse:
        ctx.devices = jax.devices()[: cell.chips]
    else:
        ctx.devices, ctx.peaks = find_devices(jax, cell.chips)
    driver = load_module("drivers", cell.traffic["driver"])

    trace_dir = None
    if trace and not rehearse:
        # The profiler runs from before set-up: under it the programs
        # are compiled again (my chip run, PR 22), and that belongs to
        # set-up, not to the window (the span `bench.window`).
        from tracereduce import profile_options

        trace_dir = os.path.join(TRACE_DIR, workload)
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
    try:
        state = driver.setup(ctx)
        outcome = driver.window(ctx, state, seed, seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    setup_s = outcome.t0 - T_START
    print(f"compiles: {ctx.compiles.compiled - outcome.compiles_in_window} "
          f"in set-up, {outcome.compiles_in_window} in the window",
          file=sys.stderr)
    device = {
        "platform": ctx.devices[0].platform,
        "kind": ctx.devices[0].device_kind,
        "count": len(jax.devices()),
    }
    if not rehearse:
        device["memory_peak_bytes"] = memory_peak_bytes(ctx.devices)
    driver.release(ctx, state)
    checks = driver.check(ctx, outcome)
    correct = judge(checks, cell.limits) and outcome.failed == 0

    if rehearse:
        return {"correct": correct, "attempted": outcome.attempted,
                "failed": outcome.failed,
                "checks": checks_line(checks, cell.limits)}
    breakdown = None
    if trace:
        from tracereduce import reduce_trace

        reduced = reduce_trace(trace_dir, ctx.devices, ctx.peaks)
        metrics = per_layer_metrics(ctx, outcome, reduced)
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        breakdown = reduced.breakdown()
    else:
        metrics = {
            m["name"]: {"value": outcome.metrics[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] != "setup_s"
        }
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    return result_line(correct, outcome.attempted, outcome.failed, metrics,
                       device, checks_line(checks, cell.limits), breakdown)


def checks_line(checks, limits) -> Dict[str, dict]:
    return {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}


def result_line(correct, attempted, failed, metrics, device, checks,
                breakdown=None) -> dict:
    """The result object in the contract's key order; the compared
    numbers, each beside its limit, come last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = checks
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU rehearsal at the configuration's tiny size; "
                    "prints no result line")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, HERE)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), args.rehearse)
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    if args.rehearse:
        print(json.dumps(result), file=sys.stderr)
        return 0 if result["correct"] else 1
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
