"""Traffic driver `solo`: one caller calls one solver entry back to back.

The entry is the one `wavetpu.cli.main` dispatches to for the cell's
scheme and `fuse_steps` (`kfused_comp.solve_kfused_comp` for the
compensated scheme, `kfused.solve_kfused` for the standard one), called
as the CLI calls it, without the report files.  Every call compiles or
loads its program, marches, and reads its error rows back.

The window closes at the end of the first solve that finishes after
`seconds`; only whole solves count.  `solve_gcells_per_s` is the
cell-updates of those solves ((N+1)^3 per step, as
`SolveResult.gcells_per_second` counts them) over the window's wall time.

The entry programs close over every input, so the seed changes nothing
that is solved: every solve of a run is the configuration's own problem,
and every solve's state is compared bit for bit with the last one's,
which is compared with the plain reference.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

import reference


@dataclass
class State:
    problem: object
    solve: Optional[Callable]
    fingerprint: Callable


@dataclass
class Outcome:
    attempted: int
    failed: int
    t0: float                # the window's start: set-up ends here
    compiles_in_window: int
    metrics: dict
    fields: tuple            # the last solve's (u_cur, u_prev[, comp_v])
    abs_errors: np.ndarray   # its per-layer error rows
    fingerprints: List = field(default_factory=list)


def _bits(a):
    wide = {2: jnp.uint16, 4: jnp.uint32}[a.dtype.itemsize]
    return jax.lax.bitcast_convert_type(a, wide).astype(jnp.uint32)


@jax.jit
def fingerprint(*arrays):
    """Two wrapping integer sums of each array's bits, one weighted by
    position: equal for equal arrays, whatever the reduction order."""
    out = []
    for a in arrays:
        b = _bits(a).ravel()
        w = jnp.arange(b.size, dtype=jnp.uint32) * jnp.uint32(2654435761) | 1
        out += [jnp.sum(b, dtype=jnp.uint32), jnp.sum(b * w, dtype=jnp.uint32)]
    return jnp.stack(out)


def _fields(scheme, res):
    if scheme == "compensated":
        return (res.u_cur, res.u_prev, res.comp_v)
    return (res.u_cur, res.u_prev)


def _entry(ctx):
    from wavetpu.core.problem import Problem

    t = ctx.cell.traffic
    problem = Problem(**ctx.problem_args)
    k = int(t["fuse_steps"])
    if t["scheme"] == "compensated":
        from wavetpu.solver import kfused_comp

        def solve():
            return kfused_comp.solve_kfused_comp(
                problem, k=k, interpret=ctx.rehearse)
    elif t["scheme"] == "standard":
        from wavetpu.solver import kfused

        def solve():
            return kfused.solve_kfused(problem, k=k, interpret=ctx.rehearse)
    else:
        raise ValueError(f"solo: unknown scheme {t['scheme']!r}")
    return problem, solve


def setup(ctx) -> State:
    return State(*_entry(ctx), fingerprint)


def window(ctx, state: State, seed: int, seconds: float,
           max_warm: int = 4) -> Outcome:
    """Warm-up calls until one compiles nothing, then the measured calls,
    all from the one line below.  The program's module carries the
    Python call stack in its locations, and JAX's cache key covers them:
    a warm-up from another line compiled a module the window could not
    use (my chip runs, PR 22).  The warm-up counts as set-up."""
    scheme = ctx.cell.traffic["scheme"]
    fps, n, res, warm, t0 = [], 0, None, 0, None
    span = jax.profiler.TraceAnnotation("bench.window")
    try:
        while True:
            before = ctx.compiles.compiled
            res = None  # the last result only is kept
            with jax.profiler.TraceAnnotation("bench.solve"):
                res = state.solve()
            fp = state.fingerprint(*_fields(scheme, res))
            if t0 is None:
                jax.block_until_ready(fp)
                warm += 1
                if ctx.compiles.compiled == before or warm == max_warm:
                    t0, c0 = time.perf_counter(), ctx.compiles.compiled
                    span.__enter__()
                continue
            fps.append(fp)
            n += 1
            if time.perf_counter() - t0 >= seconds:
                break
        jax.block_until_ready((_fields(scheme, res), fps))
        t1 = time.perf_counter()
    finally:
        if t0 is not None:
            span.__exit__(None, None, None)
    p = state.problem
    cells = p.cells_per_step * p.timesteps * n
    return Outcome(
        attempted=n, failed=0, t0=t0,
        compiles_in_window=ctx.compiles.compiled - c0,
        metrics={"solve_gcells_per_s": cells / (t1 - t0) / 1e9},
        fields=_fields(scheme, res),
        abs_errors=np.asarray(res.abs_errors, np.float64),
        fingerprints=fps,
    )


def release(ctx, state: State) -> None:
    state.solve = None


@jax.jit
def _max_gap(a, b):
    return jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))


def compare(scheme, got_fields, got_rows, ref: reference.RefOutput) -> dict:
    """The numbers compared: the largest gap of each state field and of
    the per-layer error rows."""
    names = ("u_gap", "u_prev_gap", "v_gap")
    want = (ref.u_cur, ref.u_prev, ref.v)
    out = {n: float(_max_gap(g, w)) for n, g, w in zip(names, got_fields, want)}
    rows = np.asarray(ref.abs_errors, np.float64)
    got_rows = np.asarray(got_rows, np.float64)
    out["err_rows_gap"] = (float(np.max(np.abs(got_rows - rows)))
                           if got_rows.shape == rows.shape else float("inf"))
    return out


def check(ctx, out: Outcome) -> dict:
    scheme = ctx.cell.traffic["scheme"]
    ref = reference.solve(reference.RefProblem.of(ctx.problem_args), scheme, device=ctx.devices[0])
    readings = compare(scheme, out.fields, out.abs_errors, ref)
    last = np.asarray(out.fingerprints[-1])
    readings["solves_unlike_checked"] = float(sum(
        not np.array_equal(np.asarray(f), last) for f in out.fingerprints))
    return readings


def control(ctx, out=None, dtype=jnp.bfloat16) -> dict:
    """The reference in a lower precision, in the program's place (the
    solo problem has no per-seed inputs, so `out` is not needed)."""
    scheme = ctx.cell.traffic["scheme"]
    p = reference.RefProblem.of(ctx.problem_args)
    low = reference.solve(p, scheme, dtype=dtype, device=ctx.devices[0])
    low_fields = (low.u_cur, low.u_prev, low.v)[: 3 if scheme == "compensated" else 2]
    low_rows = np.asarray(low.abs_errors, np.float64)
    ref = reference.solve(p, scheme, device=ctx.devices[0])
    return compare(scheme, low_fields, low_rows, ref)
