"""`correct` decided at the rehearsal size on the CPU: sound runs pass
their cell's limits, the control (the plain reference in bfloat16 in
the program's place) fails them, and so does every run whose timed path
is broken underneath in a way the cell can be broken:

- a step that returns its state unchanged;
- an answer altered where it is produced;
- half of a batch left out (the serve cell, whose lanes share a batch).

The exchange between chips cannot be left out: no cell spans chips.
The chip-size readings the limits are set from are in PERF.md.
"""

import numpy as np
import pytest

import run

SOLO = ["ref512-comp-solo", "ref512-std-solo"]
SERVE = ["ref256-std-serve-open"]


def rehearse(workload, seed=20260101):
    return run.run_cell(workload, seed, 2.0, False, rehearse=True)


def rehearsal_ctx(workload, seed):
    cell = run.load_cell(workload)
    run.import_system()
    jax = run.configure_jax(True)
    ctx = run.Context(cell, seed, rehearse=True)
    ctx.compiles = run.CompileWatch(jax)
    ctx.devices = jax.devices()[: cell.chips]
    return ctx, run.load_module("drivers", cell.traffic["driver"])


@pytest.mark.parametrize("workload", SOLO + SERVE)
def test_sound_run_is_correct(workload):
    res = rehearse(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("workload", SOLO + SERVE)
def test_control_is_not_correct(workload):
    ctx, driver = rehearsal_ctx(workload, 7)
    state = driver.setup(ctx)
    try:
        out = driver.window(ctx, state, 7, 1.0)
    finally:
        driver.release(ctx, state)
    readings = driver.control(ctx, out)
    assert not run.judge(readings, ctx.cell.limits), readings


# ---- faults planted underneath the timed path

def _state_unchanged_solo(monkeypatch):
    from wavetpu.solver import kfused, kfused_comp

    comp = kfused_comp._make_march

    def comp_march(*a, **k):
        march = comp(*a, **k)

        def still(u, v, c, start, *fp):
            _, _, _, ab, rl = march(u, v, c, start, *fp)
            return u, v, c, ab, rl
        return still

    std = kfused._make_march

    def std_march(*a, **k):
        march, step1, errors = std(*a, **k)

        def still(up, u, start, *fp):
            _, _, ab, rl = march(up, u, start, *fp)
            return up, u, ab, rl
        return still, step1, errors

    monkeypatch.setattr(kfused_comp, "_make_march", comp_march)
    monkeypatch.setattr(kfused, "_make_march", std_march)


def _answer_altered_solo(monkeypatch):
    from wavetpu.solver import leapfrog

    timed = leapfrog._timed_compile_run

    def altered(*a, **k):
        out, init_s, solve_s = timed(*a, **k)
        first = out[0].at[3, 3, 3].add(0.1)
        return (first,) + tuple(out[1:]), init_s, solve_s

    monkeypatch.setattr(leapfrog, "_timed_compile_run", altered)


def _state_unchanged_serve(monkeypatch):
    from wavetpu.kernels import stencil_ref

    monkeypatch.setattr(stencil_ref, "leapfrog_step", lambda up, u, p: u)


def _engine_patch(monkeypatch, change):
    from wavetpu.serve import engine

    solve = engine.ServeEngine.solve

    def patched(self, *a, **k):
        result, health = solve(self, *a, **k)
        change(result.results)
        return result, health

    monkeypatch.setattr(engine.ServeEngine, "solve", patched)


def _answer_altered_serve(monkeypatch):
    def change(results):
        for r in results:
            r.abs_errors = np.asarray(r.abs_errors) + 0.01
    _engine_patch(monkeypatch, change)


def _half_batch_left_out(monkeypatch):
    def change(results):
        half = len(results) // 2
        for i in range(half, 2 * half):
            results[i].abs_errors = np.array(results[i - half].abs_errors)
    _engine_patch(monkeypatch, change)
    # Bursts, so that the tiny CPU solves still share batches.
    driver = run.load_module("drivers", "serve_open")
    gaps = driver.gaps
    monkeypatch.setattr(driver, "gaps", lambda traffic, seconds: gaps(
        dict(traffic, rate_per_s=200.0), seconds))


def test_one_solve_unlike_the_checked_one(monkeypatch):
    """Every solve of the window is held to the checked one bit for bit:
    altering every other call's answer reads as unlike solves."""
    from wavetpu.solver import leapfrog

    timed, calls = leapfrog._timed_compile_run, []

    def altered(*a, **k):
        out, init_s, solve_s = timed(*a, **k)
        calls.append(1)
        if len(calls) % 2:
            out = (out[0].at[3, 3, 3].add(0.1),) + tuple(out[1:])
        return out, init_s, solve_s

    monkeypatch.setattr(leapfrog, "_timed_compile_run", altered)
    res = run.run_cell("ref512-std-solo", 5, 6.0, False, rehearse=True)
    assert res["attempted"] >= 2
    assert res["checks"]["solves_unlike_checked"]["value"] >= 1
    assert res["correct"] is False


@pytest.mark.parametrize("workload,fault", [
    (w, f) for w in SOLO for f in (_state_unchanged_solo, _answer_altered_solo)
] + [
    (w, f) for w in SERVE for f in (_state_unchanged_serve,
                                    _answer_altered_serve,
                                    _half_batch_left_out)
])
def test_broken_timed_path_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    res = rehearse(workload)
    assert res["correct"] is False, res["checks"]
