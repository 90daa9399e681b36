"""The readings a cell's correctness limits are set from (PERF.md, "How
`correct` is decided"): in one process, the program's timed path on a
dozen seeds or more, each in a short window at the cell's own load and
compared as a run compares it, then the control (the plain reference
computed in bfloat16, in the program's place) on three seeds or more.

    python3 benchmark/calibrate.py --workload CELL --seconds 4 \
        --seeds 11 12 ... --control-seeds 21 22 23 [--out FILE]

Prints one JSON object: each seed's compared numbers and window metric,
the control's numbers, and for each number the largest sound reading
and the smallest control reading.  Not run by the benchmark's runs.

    python3 benchmark/calibrate.py --workload CELL --seconds 15 \
        --sweep 6 7 8 9 10 11

is the knee sweep of an open-loop serve cell: one server, one window at
each offered rate, with the rate completed and the latency of the first
and the last third of the requests (a backlog that grows shows as a
last third slower than the first).
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def calibrate(workload, seeds, control_seeds, seconds):
    cell = run.load_cell(workload)
    run.use_checkout_cache()
    run.import_system()
    jax = run.configure_jax(False)
    ctx = run.Context(cell, seeds[0])
    ctx.compiles = run.CompileWatch(jax)
    ctx.devices, ctx.peaks = run.find_devices(jax, cell.chips)
    driver = run.load_module("drivers", cell.traffic["driver"])
    state = driver.setup(ctx)
    program, outcomes = [], {}
    try:
        for seed in seeds:
            ctx.seed = seed
            out = driver.window(ctx, state, seed, seconds)
            readings = driver.check(ctx, out)
            program.append({"seed": seed, "attempted": out.attempted,
                            "failed": out.failed, "metrics": out.metrics,
                            "readings": readings})
            print(json.dumps(program[-1]), file=sys.stderr, flush=True)
            if seed in control_seeds:
                outcomes[seed] = out
        for seed in control_seeds:
            if seed not in outcomes:
                ctx.seed = seed
                outcomes[seed] = driver.window(ctx, state, seed, seconds)
    finally:
        driver.release(ctx, state)
    control = []
    for seed in control_seeds:
        ctx.seed = seed
        readings = driver.control(ctx, outcomes[seed])
        control.append({"seed": seed, "readings": readings})
        print(json.dumps(control[-1]), file=sys.stderr, flush=True)
    summary = {}
    for n in program[0]["readings"]:
        ups = [c["readings"][n] for c in control if n in c["readings"]]
        summary[n] = {"lower": max(p["readings"][n] for p in program),
                      "upper": min(ups) if ups else None,
                      "limit": cell.limits.get(n)}
    return {"workload": workload, "device_kind": ctx.devices[0].device_kind,
            "program": program, "control": control, "summary": summary}


def sweep(workload, rates, seconds, seed=1):
    cell = run.load_cell(workload)
    run.use_checkout_cache()
    run.import_system()
    jax = run.configure_jax(False)
    ctx = run.Context(cell, seed)
    ctx.compiles = run.CompileWatch(jax)
    ctx.devices, ctx.peaks = run.find_devices(jax, cell.chips)
    driver = run.load_module("drivers", cell.traffic["driver"])
    state = driver.setup(ctx)
    rows = []
    try:
        for rate in rates:
            out = driver.window(ctx, state, seed, seconds, rate=rate)
            lat = [(a.done - a.sent) * 1e3 for a in out.answers if a.ok]
            third = max(1, len(lat) // 3)
            rows.append({
                "offered_per_s": rate, "attempted": out.attempted,
                "failed": out.failed, **out.metrics,
                "p95_first_third_ms": driver.percentile(sorted(lat[:third]), 0.95),
                "p95_last_third_ms": driver.percentile(sorted(lat[-third:]), 0.95),
            })
            print(json.dumps(rows[-1]), file=sys.stderr, flush=True)
    finally:
        driver.release(ctx, state)
    return {"workload": workload, "sweep": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--control-seeds", type=int, nargs="+")
    ap.add_argument("--sweep", type=float, nargs="+")
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    try:
        if args.sweep:
            res = sweep(args.workload, args.sweep, args.seconds)
        else:
            res = calibrate(args.workload, args.seeds, args.control_seeds,
                            args.seconds)
    except run.BenchError as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    text = json.dumps(res)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
