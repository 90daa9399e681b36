"""Temporally fused k-step solver (single device).

Drives `stencil_pallas.fused_kstep`: the time loop scans over BLOCKS of k
leapfrog layers, each block one pallas call that keeps the intermediate
layers in VMEM and writes only the block's last two layers to HBM - the
1-step path's ~3 HBM field-streams per step become (4 + 4k/bx)/k.  Measured
on a single v5e at the flagship N=512/1000-step config with per-layer
errors on, the solve alone: 71.8 Gcell/s at k=4, where the 1-step kernel
measured 20.3 Gcell/s.

Per-layer L-inf abs/rel errors remain reported for EVERY layer - the
kernel emits per-x-plane maxes for the in-VMEM intermediate layers (the
separable-oracle factorization, stencil_pallas.py section comment), and
this module applies the tiny per-plane rescales and the x!=0 interior
mask outside (reference error contract: mpi_new.cpp:335-345,
openmp_sol.cpp:169-190).

Each substep is op-for-op the 1-step pallas kernel's update, so k-fused
layers are bitwise identical to 1-step pallas layers: a solve may stop at
any layer (`stop_step`), checkpoint, and resume with either path
(tests/test_kfused.py pins this).

The reference has no counterpart to fuse-k (every variant launches one
kernel per layer with a global sync between); SURVEY.md section 7's perf
plan called the HBM stream count the budget to beat, and this is the
mechanism that beats it.

Variable wave speed composes with the onion: `c2tau2_field` threads the
tau^2 c^2(x,y,z) slab through every k-block as its own onion (slab +
k-plane halos, stencil_pallas._kstep_kernel has_field) and through the
1-step bootstrap/remainder kernels, keeping the bitwise-mixing contract
with the 1-step variable-c path (tests/test_kfused_varc.py).  The field
onion's VMEM cost caps the block choice (choose_kstep_block field=True:
k=2/bx=4 at N=512 under the calibrated budget; k=4/bx=4 models ~5% over
the physical ceiling and stays reachable via an explicit block_x for
on-chip attempts).
There is no analytic oracle for variable c, so a field requires
compute_errors=False.  The compensated (Kahan) scheme takes the field
through solver/kfused_comp.py's velocity-form onion.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from wavetpu.core.problem import Problem
from wavetpu.kernels import stencil_pallas, stencil_ref
from wavetpu.obs import metrics as obs_metrics
from wavetpu.obs import tracing
from wavetpu.solver import leapfrog
from wavetpu.verify import oracle


def _oracle_parts(problem: Problem, f_dtype, phase: float = oracle.TWO_PI):
    """Precomputed separable-oracle pieces for the in-kernel error path.

    syz / rsyz are the (N, N) planes sy*sz and 1/|sy*sz| (exact-zero cells
    -> 0: there u = f = 0 and the reference's NaN-skip reports 0,
    oracle.layer_errors).  inv_absx is the per-x-plane rescale 1/|sx| with
    the x=0 interior exclusion and exact zeros folded in.  `phase` is the
    analytic solution's time phase (per-lane in the ensemble engine).
    """
    sx, sy, sz = oracle.spatial_factors(problem, f_dtype)
    ct = oracle.time_factor_table(problem, f_dtype, phase)
    syz = sy[:, None] * sz[None, :]
    rsyz = jnp.where(
        syz == 0, jnp.asarray(0, f_dtype),
        1.0 / jnp.where(syz == 0, jnp.asarray(1, f_dtype), syz),
    )
    rsyz = jnp.abs(rsyz)
    absx = jnp.abs(sx)
    xmask = jnp.asarray(np.arange(problem.N) != 0)
    inv_absx = jnp.where(
        xmask & (absx != 0),
        1.0 / jnp.where(absx == 0, jnp.asarray(1, f_dtype), absx),
        jnp.asarray(0, f_dtype),
    )
    return sx, ct, syz, rsyz, xmask, inv_absx


def _layer_rows_local(u, sxct_row, syz_c, rsyz_c, f):
    """(1, nl) per-x-plane abs/rel error maxes of one stored layer's local
    block vs its oracle slice - the jnp bootstrap-layer counterpart of the
    kernels' in-onion rows, shared by every sharded k-fused solver (a
    change to this contract must not diverge between them)."""
    diff = jnp.abs(u.astype(f) - sxct_row[:, None, None] * syz_c[None])
    d = jnp.max(diff, axis=(1, 2))[None]
    r = jnp.max(diff * rsyz_c[None], axis=(1, 2))[None]
    return d, r


def _block_errors(dmax, rmax, ctk, xmask, inv_absx):
    """(k,) abs / rel layer errors from the kernel's (k, N) plane maxes."""
    abs_e = jnp.max(jnp.where(xmask[None, :], dmax, 0.0), axis=1)
    rel_e = jnp.max(
        jnp.where(xmask[None, :], rmax * inv_absx[None, :], 0.0), axis=1
    )
    ictk = jnp.abs(ctk)
    rel_e = jnp.where(
        ictk != 0, rel_e / jnp.where(ictk == 0, 1.0, ictk), 0.0
    )
    return abs_e, rel_e


def _validate(problem: Problem, k: int, c2tau2_field=None,
              compute_errors: bool = True,
              phase: float = oracle.TWO_PI):
    if k < 2:
        raise ValueError(f"k must be >= 2 (got {k}); use leapfrog.solve "
                         "with the pallas step for k=1")
    if problem.N % k:
        raise ValueError(f"k={k} must divide N={problem.N}")
    if c2tau2_field is not None and compute_errors:
        raise ValueError(
            "variable-c runs have no analytic oracle; pass "
            "compute_errors=False with c2tau2_field"
        )
    if c2tau2_field is not None and phase != oracle.TWO_PI:
        raise ValueError(
            "a shifted phase bootstraps layer 1 from the analytic "
            "solution, which only exists for constant speed; use the "
            "reference phase with c2tau2_field"
        )


def _make_march(problem, dtype, k, compute_errors, block_x, interpret,
                nsteps, c2tau2_field=None, chunk_len=None,
                phase: float = oracle.TWO_PI):
    """Shared march: k-fused blocks + a 1-step remainder tail.

    `make_kfused_solver`, `resume_kfused`, and `make_chunk_runner` MUST
    use this single implementation - the bitwise-equal-resume guarantee
    rests on every path emitting the identical per-layer op sequence (the
    same reasoning as leapfrog._scan_layers being shared).

    Returns `march(u_prev, u_cur, start)` -> (u_prev, u_cur, abs, rel)
    covering layers start+1..nsteps (`start` must be a Python int).  With
    `chunk_len` set, the march instead covers exactly chunk_len layers
    from a RUNTIME `start` (nblocks/remainder derive from chunk_len, so
    one compiled program serves every equal-length chunk of a supervised
    march); on block-aligned starts the op sequence equals the
    uninterrupted march's prefix.

    With `c2tau2_field` every k-block runs the variable-c onion and the
    bootstrap/remainder run the 1-step variable-c pallas kernel - the
    same ParamStep plumbing as leapfrog.make_solver, so the field is a
    runtime argument, never an HLO literal.
    """
    f = stencil_ref.compute_dtype(dtype)
    sx, ct, syz, rsyz, xmask, inv_absx = _oracle_parts(problem, f, phase)
    errors = leapfrog._error_fn(problem, dtype, phase)
    # The field enters the jitted program as a RUNTIME argument (the
    # `*field_params` splat below: () constant-c, (field,) variable-c) -
    # closing over it would embed an N^3 HLO literal (leapfrog.ParamStep).
    step1 = stencil_pallas.make_step_fn(
        interpret=interpret, c2tau2_field=(
            None if c2tau2_field is None
            else jnp.asarray(c2tau2_field, dtype=f)
        )
    )
    step1_fn, params0 = leapfrog._as_param_step(step1)
    has_field = c2tau2_field is not None

    def kblock(carry, nstart, field_params):
        u_prev, u = carry
        ctk = lax.dynamic_slice(ct, (nstart + 1,), (k,))
        sxct = ctk[:, None] * sx[None, :]
        up, uc, dmax, rmax = stencil_pallas.fused_kstep(
            u_prev, u, syz, rsyz, sxct,
            k=k, coeff=problem.a2tau2, inv_h2=problem.inv_h2,
            c2tau2_field=field_params[0] if has_field else None,
            block_x=block_x, interpret=interpret,
            with_errors=compute_errors,
        )
        if compute_errors:
            abs_e, rel_e = _block_errors(dmax, rmax, ctk, xmask, inv_absx)
        else:
            abs_e = rel_e = jnp.zeros((k,), f)
        return (up, uc), (abs_e, rel_e)

    def march(u_prev, u_cur, start, *field_params):
        if chunk_len is None:
            nblocks = (nsteps - start) // k
            rem = (nsteps - start) - nblocks * k
        else:
            nblocks = chunk_len // k
            rem = chunk_len - nblocks * k
        starts = start + k * jnp.arange(nblocks)
        # Two blocks a loop turn: with one pallas_call per turn the call
        # reads the carried fields while writing the new ones, so XLA
        # copies every state field before each call.  With two, the
        # second call's outputs take the buffers the first call's inputs
        # freed, and no field is copied.  Same kernels in the same order,
        # so the march stays bitwise what it was.
        (u_prev, u_cur), (abs_b, rel_b) = lax.scan(
            lambda carry, nstart: kblock(carry, nstart, field_params),
            (u_prev, u_cur), starts, unroll=2,
        )
        abs_parts = [abs_b.reshape(-1)]
        rel_parts = [rel_b.reshape(-1)]
        if rem:
            params = field_params[0] if has_field else params0
            rem_start = (
                nsteps - rem if chunk_len is None
                else start + chunk_len - rem
            )
            (u_prev, u_cur), (ra, rr) = leapfrog._scan_layers_xs(
                problem, step1_fn, params, errors, compute_errors, dtype,
                u_prev, u_cur,
                rem_start + 1 + jnp.arange(rem, dtype=jnp.int32),
            )
            abs_parts.append(ra)
            rel_parts.append(rr)
        return u_prev, u_cur, jnp.concatenate(abs_parts), jnp.concatenate(
            rel_parts)

    return march, step1_fn, errors


def make_kfused_solver(
    problem: Problem,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    block_x: Optional[int] = None,
    interpret: bool = False,
    c2tau2_field=None,
    phase: float = oracle.TWO_PI,
):
    """Build the jitted k-fused solver; returns `(runner, run_params)`
    where `run_params` is the runtime-argument tuple to call the runner
    with - () for constant speed (a zero-arg runner, as before), or the
    materialized device field for a variable-c solve (the field must ride
    as an argument, not a constant; see leapfrog.ParamStep).

    Layers 0/1 bootstrap exactly as `leapfrog.make_solver` with the pallas
    1-step kernel; then (nsteps-1)//k fused blocks; a remainder of
    (nsteps-1) % k layers runs the 1-step kernel (same ops, so the tail is
    seamless).  Requires k >= 2 and N % k == 0; a field requires
    compute_errors=False (no analytic oracle) and the reference phase
    (a shifted phase needs the analytic layer-1 bootstrap, which does
    not exist under variable c).
    """
    _validate(problem, k, c2tau2_field, compute_errors, phase)
    nsteps = problem.timesteps if stop_step is None else stop_step
    if not 1 <= nsteps <= problem.timesteps:
        raise ValueError(
            f"stop_step must be in [1, {problem.timesteps}], got {nsteps}"
        )
    f = stencil_ref.compute_dtype(dtype)
    # Materialize the field ONCE; _make_march's jnp.asarray on this
    # committed device array is a no-copy, so the step closure and the
    # runtime argument share one N^3 buffer (no duplicate HBM/upload).
    field_dev = None
    if c2tau2_field is not None:
        field_dev = leapfrog.ParamStep.materialize(
            jnp.asarray(c2tau2_field, dtype=f)
        )
    march, step1_fn, errors = _make_march(
        problem, dtype, k, compute_errors, block_x, interpret, nsteps,
        field_dev, phase=phase,
    )

    def run(*field_params):
        u0 = leapfrog.initial_layer0(problem, dtype, phase)
        params = field_params[0] if field_params else ()
        if phase != oracle.TWO_PI:
            # Shifted phases have nonzero initial velocity, which the
            # step-derived Taylor bootstrap cannot represent; layer 1 is
            # the exact analytic initialization instead (statically
            # absent at the reference phase - see leapfrog.make_solver).
            u1 = leapfrog.analytic_layer(problem, dtype, phase, 1)
        else:
            u1 = (0.5 * (
                u0.astype(f) + step1_fn(u0, u0, problem, params).astype(f)
            )).astype(dtype)
        a0 = r0 = jnp.zeros((), f)
        if compute_errors:
            a1, r1 = errors(u1, 1)
        else:
            a1 = r1 = jnp.zeros((), f)
        u_prev, u_cur, abs_t, rel_t = march(u0, u1, 1, *field_params)
        abs_all = jnp.concatenate([jnp.stack([a0, a1]), abs_t])
        rel_all = jnp.concatenate([jnp.stack([r0, r1]), rel_t])
        return u_prev, u_cur, abs_all, rel_all

    run_params = () if field_dev is None else (field_dev,)
    return jax.jit(run), run_params


def solve_kfused(
    problem: Problem,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    block_x: Optional[int] = None,
    interpret: bool = False,
    c2tau2_field=None,
    phase: float = oracle.TWO_PI,
) -> leapfrog.SolveResult:
    """Compile + run the k-fused solve (reference timing phases as
    `leapfrog.solve`).  `c2tau2_field` (host (N,N,N) tau^2 c^2 array,
    `stencil_ref.make_c2tau2_field`) selects the variable-c onion; pair
    it with compute_errors=False."""
    runner, run_params = make_kfused_solver(
        problem, dtype, k, compute_errors, stop_step, block_x, interpret,
        c2tau2_field, phase,
    )
    (u_prev, u_cur, abs_all, rel_all), init_s, solve_s = (
        leapfrog._timed_compile_run(
            runner, run_params, sync=lambda out: np.asarray(out[2]),
            path="kfused", scheme="standard", k=k, n=problem.N,
        )
    )
    with tracing.span("solve.finish", path="kfused"):
        result = leapfrog.SolveResult(
            problem=problem,
            u_prev=u_prev,
            u_cur=u_cur,
            abs_errors=np.asarray(abs_all, dtype=np.float64),
            rel_errors=np.asarray(rel_all, dtype=np.float64),
            init_seconds=init_s,
            solve_seconds=solve_s,
            steps_computed=stop_step,
            final_step=(stop_step if stop_step is not None
                        else problem.timesteps),
        )
        obs_metrics.record_solve(
            result, "kfused", k=k, with_field=c2tau2_field is not None,
            block_x=block_x,
        )
    return result


def resume_kfused(
    problem: Problem,
    u_prev,
    u_cur,
    start_step: int,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    block_x: Optional[int] = None,
    interpret: bool = False,
    c2tau2_field=None,
) -> leapfrog.SolveResult:
    """Re-enter the k-fused march at layer `start_step`.

    Because every k-fused substep is op-identical to the 1-step pallas
    kernel's step, a checkpoint written by either path resumes bitwise-
    equal under either path (error arrays cover start_step+1..timesteps,
    earlier entries zero, as `leapfrog.resume`).  A variable-c checkpoint
    resumes under the SAME field, re-passed by the caller (checkpoints
    store state, not the coefficient field).
    """
    _validate(problem, k, c2tau2_field, compute_errors)
    nsteps = problem.timesteps
    if not 1 <= start_step <= nsteps:
        raise ValueError(
            f"start_step must be in [1, {nsteps}], got {start_step}"
        )
    f = stencil_ref.compute_dtype(dtype)
    # One materialization shared by the step closure and the runtime
    # argument (see make_kfused_solver).
    field_dev = None
    if c2tau2_field is not None:
        field_dev = leapfrog.ParamStep.materialize(
            jnp.asarray(c2tau2_field, dtype=f)
        )
    march, _, _ = _make_march(
        problem, dtype, k, compute_errors, block_x, interpret, nsteps,
        field_dev,
    )

    def run(u_prev, u_cur, *field_params):
        u_prev, u_cur, abs_t, rel_t = march(
            u_prev, u_cur, start_step, *field_params
        )
        head = jnp.zeros((start_step + 1,), f)
        return (
            u_prev, u_cur,
            jnp.concatenate([head, abs_t]),
            jnp.concatenate([head, rel_t]),
        )

    args = (jnp.asarray(u_prev, dtype), jnp.asarray(u_cur, dtype))
    if field_dev is not None:
        args = args + (field_dev,)
    (u_p, u_c, abs_all, rel_all), init_s, solve_s = (
        leapfrog._timed_compile_run(
            jax.jit(run), args, sync=lambda out: np.asarray(out[2]),
            path="kfused", scheme="standard", k=k, n=problem.N,
        )
    )
    return leapfrog.SolveResult(
        problem=problem,
        u_prev=u_p,
        u_cur=u_c,
        abs_errors=np.asarray(abs_all, dtype=np.float64),
        rel_errors=np.asarray(rel_all, dtype=np.float64),
        init_seconds=init_s,
        solve_seconds=solve_s,
        steps_computed=nsteps - start_step,
        final_step=nsteps,
    )


def make_chunk_runner(
    problem: Problem,
    dtype=jnp.float32,
    length: int = 4,
    k: int = 4,
    compute_errors: bool = True,
    block_x: Optional[int] = None,
    interpret: bool = False,
    c2tau2_field=None,
):
    """Fixed-length k-fused re-entry for supervised solves.

    Returns `(runner, run_params)`; `runner(u_prev, u_cur, start,
    *run_params)` marches layers start+1..start+length with a RUNTIME
    `start` - one compiled program per chunk length, reused across every
    chunk (run/supervisor.py's no-retrace contract).  Chunks whose length
    is a multiple of k on starts aligned to the uninterrupted march's
    block grid reproduce its op sequence exactly; a trailing length % k
    runs the 1-step kernel, as the uninterrupted remainder tail does.
    """
    _validate(problem, k, c2tau2_field, compute_errors)
    if length < 1:
        raise ValueError(f"chunk length must be >= 1, got {length}")
    f = stencil_ref.compute_dtype(dtype)
    field_dev = None
    if c2tau2_field is not None:
        field_dev = leapfrog.ParamStep.materialize(
            jnp.asarray(c2tau2_field, dtype=f)
        )
    march, _, _ = _make_march(
        problem, dtype, k, compute_errors, block_x, interpret, None,
        field_dev, chunk_len=length,
    )

    def run(u_prev, u_cur, start, *field_params):
        return march(u_prev, u_cur, start, *field_params)

    run_params = () if field_dev is None else (field_dev,)
    return jax.jit(run), run_params
