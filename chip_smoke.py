"""Chip smoke test: the solve and serve paths, end to end on a TPU.

    python chip_smoke.py               one chip: the headline solves
                                       through the CLI, then the serve path
    python chip_smoke.py --four-chip   four chips: the sharded k=4 solves
                                       against the single-device ones, and
                                       nothing else

Everything runs in this one process: a chip belongs to one process, so
nothing here starts a child that would need it.  Any failed phase exits
non-zero; on success the last line of stdout is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}`.
Times printed on the way are smoke readings of one cold (or cache-warm)
run, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import os
import sys
import tempfile
import threading
import urllib.error
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))

# The headline problem (README): N=512, 1000 steps, L=T=1.
HEADLINE = ["512", "1", "1", "1", "1", "1", "1000"]
# Per-layer L-inf bounds at the headline size: the compensated onion
# reaches the f32 discretization class (5.7e-6 recorded), standard k=4
# is rounding-dominated (1.1e-3 recorded).
COMP_MAX_ERR = 1e-5
STD_MAX_ERR = 2e-3
# Served requests: N=256/500 has the headline's Courant number (both
# tau/h = 0.512).  Its discretization error is 4x the headline's (h^2):
# 2.1e-5 in f64 (leapfrog.solve, my CPU run), so the compensated bound
# scales with it; the standard scheme keeps its rounding bound.
SERVE_N, SERVE_STEPS = 256, 500
SERVE_COMP_MAX_ERR = 3e-5
# Standard k=4 across meshes: the state must be bitwise equal, so the
# per-layer errors can differ only in how the analytic reference was
# rounded (layer 1: a full-field product on one device, per-plane rows
# on the mesh; each rounds four factors in its own order).  |exact| <= 1,
# so that is a few f32 ulps of 1.0 at most (the chip showed one, 2^-23,
# at layer 1, my chip run, PR 21).
STD_ERR_ATOL = 4 * 2.0 ** -23
# Cross-mesh agreement of the compensated onion's state, as
# tests/test_kfused_comp.py::test_sharded_xy_matches_single_device pins.
# A per-layer error is a max of |u - exact|, so it cannot move further
# than u does: the error rows are held to the same bound.  (The test's
# tighter rtol 1e-3 / atol 1e-7 on the rows holds at its N=32/21 only:
# at N=64/21 on 4 CPU devices the rows already differ by 2.4e-7.)
MESH_U_ATOL = 1e-6


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def check_errors(name: str, errs, bound: float) -> float:
    """Every per-layer error finite, the largest within `bound`."""
    require(errs is not None and len(errs) > 0, f"{name}: no error rows")
    require(all(math.isfinite(e) for e in errs),
            f"{name}: non-finite per-layer errors")
    worst = max(errs)
    require(worst <= bound, f"{name}: max L-inf {worst:.3e} > {bound:g}")
    return worst


def device_check(want_count: int):
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SmokeFailure(
            f"needs a TPU; JAX found platform {dev.platform!r} "
            f"({dev.device_kind}, {len(devs)} device(s))"
        )
    require(len(devs) >= want_count,
            f"needs {want_count} TPU devices, found {len(devs)}")
    say(f"device: {dev.device_kind} x{len(devs)}")
    return dev, len(devs)


# ------------------------------------------------------------ solve path


def solve_phase(out_root: str, argv, name: str, bound: float) -> None:
    """One solve through `wavetpu.cli.main`, checked from its report."""
    from wavetpu import cli

    out = os.path.join(out_root, name)
    rc = cli.main(list(argv) + ["--out-dir", out])
    require(rc == 0, f"{name}: wavetpu CLI exited {rc}")
    (report,) = [f for f in os.listdir(out) if f.endswith(".json")]
    with open(os.path.join(out, report)) as f:
        rep = json.load(f)
    worst = check_errors(name, rep["abs_errors"], bound)
    say(
        f"{name}: max L-inf {worst:.3e} (bound {bound:g}); smoke reading, "
        f"not a benchmark: {rep['gcells_per_second']:.2f} Gcell/s, "
        f"solve {rep['solve_seconds']:.3f} s, "
        f"compile+init {rep['init_seconds']:.3f} s"
    )


# ------------------------------------------------------------ serve path


def _post(base: str, body: dict):
    req = urllib.request.Request(
        base + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=900) as r:
            return r.status, json.loads(r.read()), r.headers
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), e.headers


def _timing_ms(header: str) -> dict:
    """{name: ms} from a `Server-Timing` header (`name;dur=ms, ...`)."""
    out = {}
    for part in (header or "").split(","):
        name, _, rest = part.strip().partition(";dur=")
        if rest:
            out[name] = float(rest)
    return out


def serve_wave(base: str, name: str, bodies, bound: float) -> None:
    """Fire `bodies` concurrently; each must come back 200, batched with
    the others, with its own report within `bound`."""
    with concurrent.futures.ThreadPoolExecutor(len(bodies)) as pool:
        answers = list(pool.map(lambda b: _post(base, b), bodies))
    rows = set()
    for body, (code, ans, headers) in zip(bodies, answers):
        tag = f"{name} phase={body['phase']}"
        require(code == 200, f"{tag}: HTTP {code}: {ans}")
        batch, rep = ans["batch"], ans["report"]
        require(batch["occupancy"] > 1, f"{tag}: occupancy {batch}")
        require(rep["final_step"] == body["timesteps"],
                f"{tag}: final_step {rep['final_step']}")
        worst = check_errors(tag, rep["abs_errors"], bound)
        rows.add(tuple(rep["abs_errors"]))
        ms = _timing_ms(headers.get("Server-Timing"))
        say(
            f"{tag}: 200, path {batch['path']}, occupancy "
            f"{batch['occupancy']}/{batch['batch_size']}, warm "
            f"{batch['warm']}, max L-inf {worst:.3e}; smoke reading, not "
            f"a benchmark: compile {ms.get('compile', 0.0) / 1e3:.3f} s, "
            f"execute {ms.get('execute', 0.0) / 1e3:.3f} s, total "
            f"{ms.get('total', 0.0) / 1e3:.3f} s, "
            f"{batch['aggregate_gcells_per_s']:.2f} Gcell/s aggregate"
        )
    # Distinct phases give distinct error rows: each answer is its own.
    require(len(rows) == len(bodies), f"{name}: reports not distinct")


def serve_phase(n: int, steps: int) -> None:
    from wavetpu.serve import api, progcache

    # max_wait long enough that a concurrent wave lands in one batch.
    httpd, state = api.build_server(port=0, max_wait=0.5)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    try:
        size = {"N": n, "timesteps": steps}
        serve_wave(base, "serve standard", [
            dict(size, phase=p) for p in (6.283185307179586, 1.0, 0.5, 0.25)
        ], STD_MAX_ERR)
        serve_wave(base, "serve compensated", [
            dict(size, phase=p, scheme="compensated") for p in (1.0, 0.5)
        ], SERVE_COMP_MAX_ERR)
        say(f"AOT probe: {progcache.aot_capability()}")
    finally:
        state.begin_drain(httpd)
        thread.join(timeout=60)
        state.batcher.close(timeout=120.0, drain=True)
        httpd.server_close()
    require(not thread.is_alive(), "server thread did not stop")
    say("serve: drained and stopped")


# ------------------------------------------------------------ four chips


def _spans_devices(name: str, arr, devices) -> None:
    """The array's shards sit one on each device, each a 1/len share,
    and each device's allocator holds at least its shard."""
    shards = arr.addressable_shards
    on = {s.device for s in shards}
    require(on == set(devices) and len(shards) == len(devices),
            f"{name}: shards on {sorted(d.id for d in on)}")
    sizes = [s.data.nbytes for s in shards]
    require(len(set(sizes)) == 1 and sizes[0] * len(devices) == arr.nbytes,
            f"{name}: uneven shards {sizes} of {arr.nbytes}")
    in_use = {}
    for s in shards:
        stats = s.device.memory_stats()
        in_use[s.device.id] = stats["bytes_in_use"]
        require(stats["bytes_in_use"] >= s.data.nbytes,
                f"{name}: device {s.device.id} holds "
                f"{stats['bytes_in_use']} B < its shard {s.data.nbytes} B")
    say(f"{name}: {len(shards)} shards of {sizes[0]} B (1/{len(devices)} "
        f"of {arr.nbytes} B); bytes_in_use by device {in_use}")


def _unequal(name: str, got, want) -> str:
    """How two arrays differ: count, largest gap, where along axis 0."""
    import numpy as np

    ne = np.asarray(got) != np.asarray(want)
    gap = np.abs(np.asarray(got, np.float64) - np.asarray(want, np.float64))
    where = np.nonzero(ne.reshape(ne.shape[0], -1).any(axis=1))[0]
    return (f"{name}: {int(ne.sum())} of {ne.size} differ, max "
            f"{gap.max():.3e}, along axis 0 at {len(where)} indices from "
            f"{where[:6].tolist()}")


def _reading(name: str, res) -> str:
    return (f"{name}: smoke reading, not a benchmark: "
            f"{res.gcells_per_second:.2f} Gcell/s, solve "
            f"{res.solve_seconds:.3f} s, compile+init "
            f"{res.init_seconds:.3f} s")


def four_chip_standard(problem, devices) -> None:
    """Standard k=4 on (4,1,1) against the single-device solve: the
    state bitwise equal, the per-layer errors within STD_ERR_ATOL."""
    import numpy as np

    from wavetpu.solver import kfused, sharded, sharded_kfused

    single = kfused.solve_kfused(problem, k=4)
    say(_reading("single-device standard k=4", single))
    want = (np.asarray(single.u_prev), np.asarray(single.u_cur),
            np.asarray(single.abs_errors))
    del single
    got = sharded_kfused.solve_sharded_kfused(
        problem, mesh_shape=(4, 1, 1), k=4, devices=devices
    )
    say(_reading("mesh (4,1,1) standard k=4", got))
    _spans_devices("mesh (4,1,1) u_cur", got.u_cur, devices)
    check_errors("mesh (4,1,1) standard k=4", list(got.abs_errors),
                 STD_MAX_ERR)
    errs = np.asarray(got.abs_errors)
    unequal = [
        _unequal(name, a, b) for name, a, b in (
            ("u_prev", sharded.gather_fundamental(got.u_prev, problem),
             want[0]),
            ("u_cur", sharded.gather_fundamental(got.u_cur, problem),
             want[1]),
        ) if not np.array_equal(a, b)
    ]
    require(not unequal, "mesh (4,1,1) standard k=4: state not bitwise "
            f"equal to single-device: {'; '.join(unequal)}")
    require(np.allclose(errs, want[2], rtol=0.0, atol=STD_ERR_ATOL),
            f"mesh (4,1,1) standard k=4: per-layer errors disagree with "
            f"single-device: {_unequal('abs_errors', errs, want[2])}")
    same = ("bitwise equal" if np.array_equal(errs, want[2])
            else _unequal("equal within tolerance", errs, want[2]))
    say(f"mesh (4,1,1) standard k=4: u_prev and u_cur bitwise equal to "
        f"single-device; per-layer errors {same}")


def four_chip_compensated(problem, devices) -> None:
    """Compensated k=4 on (2,2,1), the y-sharded onion kernel, against
    the single-device compensated solve, to the tolerance
    tests/test_kfused_comp.py pins."""
    import numpy as np

    from wavetpu.solver import kfused_comp, sharded

    single = kfused_comp.solve_kfused_comp(problem, k=4)
    say(_reading("single-device compensated k=4", single))
    want_u, want_err = np.asarray(single.u_cur), np.asarray(single.abs_errors)
    del single
    got = kfused_comp.solve_kfused_comp_sharded(
        problem, mesh_shape=(2, 2, 1), k=4, devices=devices
    )
    say(_reading("mesh (2,2,1) compensated k=4", got))
    _spans_devices("mesh (2,2,1) u_cur", got.u_cur, devices)
    worst = check_errors("mesh (2,2,1) compensated k=4",
                         list(got.abs_errors), COMP_MAX_ERR)
    errs = np.asarray(got.abs_errors)
    du = float(np.abs(
        sharded.gather_fundamental(got.u_cur, problem).astype(np.float64)
        - want_u
    ).max())
    gap = float(np.abs(errs.astype(np.float64) - want_err).max())
    require(du < MESH_U_ATOL and gap < MESH_U_ATOL,
            f"mesh (2,2,1) compensated: max |u - u_single| {du:.3e}, "
            f"largest per-layer error gap {gap:.3e}; bound {MESH_U_ATOL:g}")
    say(f"mesh (2,2,1) compensated k=4: max L-inf {worst:.3e}, max "
        f"|u - u_single| {du:.3e}, largest per-layer error gap {gap:.3e} "
        f"(both < {MESH_U_ATOL:g})")


def four_chip_phase(problem) -> None:
    import jax

    devices = jax.devices()[:4]
    four_chip_standard(problem, devices)
    four_chip_compensated(problem, devices)


# ------------------------------------------------------------ main


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip mesh path")
    args = ap.parse_args(argv)
    try:
        import wavetpu
    except ImportError as e:
        raise SmokeFailure(f"wavetpu is not importable next to "
                           f"chip_smoke.py: {e}") from None
    require(os.path.dirname(os.path.dirname(
        os.path.abspath(wavetpu.__file__))) == HERE,
        f"imported wavetpu from {wavetpu.__file__}, not from {HERE}")
    dev, count = device_check(4 if args.four_chip else 1)

    from wavetpu import jaxcache

    say(f"JAX compilation cache: {jaxcache.configure()}")
    if args.four_chip:
        from wavetpu.core.problem import Problem

        four_chip_phase(Problem(N=512, timesteps=1000))
    else:
        with tempfile.TemporaryDirectory(prefix="chip-smoke-") as out:
            solve_phase(out, HEADLINE + ["--scheme", "compensated",
                                         "--fuse-steps", "4"],
                        "headline compensated k=4", COMP_MAX_ERR)
            solve_phase(out, HEADLINE + ["--fuse-steps", "4"],
                        "standard k=4", STD_MAX_ERR)
        serve_phase(SERVE_N, SERVE_STEPS)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count,
    }}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        sys.exit(f"chip_smoke: FAILED: {e}")
