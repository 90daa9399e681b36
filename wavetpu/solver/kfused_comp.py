"""Compensated (velocity-form) temporally fused k-step solver.

The round-4 flagship gap was fast OR accurate: the standard k-fused onion
(solver/kfused.py) runs 71.8 Gcell/s at L-inf ~1.1e-3 (rounding-dominated),
the 1-step compensated scheme 12.4 Gcell/s at 5.7e-6 (discretization-
limited).  This module is both at once - the reference's own contract,
whose flagship runs full speed at full accuracy (all-double,
cuda_sol_kernels.cu:24-47 with the error fused at :41-45).

Mechanism: the k-step VMEM onion marches the INCREMENT form

    v_{n+1} = v_n + C*lap(u_n)
    u_{n+1} = u_n + v_{n+1}      (Kahan two-sum through `carry`)

(`stencil_ref.compensated_step` semantics) instead of the standard
2u - u_prev form.  u and v ride the onion exactly like (u_prev, u) in the
standard onion - same HBM traffic for the pair - and the carry adds one
slab-only stream (no halos: halo-cone carries seed to zero, a
second-order approximation through the Laplacian; see
`stencil_pallas._kstep_comp_kernel`).  Measured on v5e at N=512/1000,
errors fused on every layer, the solve alone: 66.2 Gcell/s at L-inf
5.72e-6 (k=4, bf16 carry), where the 1-step compensated path measured
12.4 Gcell/s at 5.69e-6 and k=2 22.3.

With `v_dtype=bfloat16` and `carry=False` the same march becomes the
increment-form bf16 mode (BASELINE config 5 re-scoped to numbers that
mean something): the increment stream stores bf16, u stays the f32
carrier, and the bf16 quantization error ~|v|*2^-8 per step stays far
below the O(1) solution - unlike a bf16 u, whose per-step increments sit
below the bf16 ulp and whose trajectory is garbage (round-4 BENCH: 0.66
L-inf).  Measured: 73.1 Gcell/s at L-inf 6.39e-4 (k=4, N=512/1000, the
solve alone).

Unlike the standard k-fused path there is NO bitwise-parity claim against
the 1-step scheme (intermediate layers skip the storage round-trip, halo
carries differ); the contract is tolerance parity vs f64
(tests/test_kfused_comp.py) and the remainder tail runs the SAME kernel
at k=1, so stop/resume stays self-consistent.

`solve_kfused_comp_sharded` distributes the scheme over (MX, MY, 1)
meshes with k-deep ghost exchange per k layers per axis (u and v ship;
the carry stays shard-local, zero-seeded in halos exactly as on one
device; on 2D meshes the y-row extension ships first and the x ghosts
ride the extended blocks, corner data via the sequencing).  x-only at
N=512 is VMEM-bound to k=2 (the four full-plane ghost buffers push k=4
to a measured 148.6 MB; k=2 runs 14.6 Gcell/s at 5.75e-6 on v5e vs 12.4
for the 1-step compensated sharded path); y-sharding shrinks every VMEM
plane by MY and restores k=4 (Mosaic-validated on chip at nl_y=64).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from wavetpu.core.problem import Problem
from wavetpu.kernels import stencil_pallas, stencil_ref
from wavetpu.obs import metrics as obs_metrics
from wavetpu.obs import tracing
from wavetpu.solver import kfused, leapfrog
from wavetpu.verify import oracle


def _default_carry_dtype(dtype):
    """bf16 carry for f32 runs, else the state dtype.

    The carry holds ~ulp(u)-scale residuals; bf16 quantizes it at
    ~carry * 2^-8 per step (~1e-10 absolute for f32 runs) - invisible at
    the f32 discretization error scale while halving the carry's HBM
    stream.  Measured back-to-back on v5e at N=512/1000 k=4: 36.50 vs
    34.34 Gcell/s with a bit-identical reported max error (5.722e-6).
    f64 runs keep an f64 carry (conservatism; the stream is not the
    bottleneck there)."""
    return jnp.bfloat16 if jnp.dtype(dtype) == jnp.float32 else dtype


def _validate_carry_dtype(dtype, carry_dtype):
    """Allowed carry storages: the state dtype, or bf16 for f32 runs.

    A bf16 carry under f64 would quantize the f64 Kahan residual at 2^-8
    and destroy the accuracy contract the carry exists to uphold; any
    non-float dtype would fail opaquely inside the kernel."""
    cd = jnp.dtype(carry_dtype)
    ok = cd == jnp.dtype(dtype) or (
        cd == jnp.bfloat16 and jnp.dtype(dtype) == jnp.float32
    )
    if not ok:
        raise ValueError(
            f"carry_dtype {cd.name} is invalid for state dtype "
            f"{jnp.dtype(dtype).name}: use the state dtype, or bfloat16 "
            f"for float32 runs"
        )


def _normalize_carry(carry, dtype):
    """Resume-side carry normalization: preserve a valid stored dtype
    (bitwise resume of bf16-carry checkpoints) WITHOUT copying or
    touching a device (jnp.result_type probes dtype only - the caller's
    placement decides where the array lands); cast anything else to the
    state dtype (e.g. an f64-interpret checkpoint resumed as f32 - an
    f64 carry ref cannot lower on TPU)."""
    cd = jnp.result_type(carry)
    if cd == jnp.dtype(dtype) or (
        cd == jnp.bfloat16 and jnp.dtype(dtype) == jnp.float32
    ):
        return carry
    return jnp.asarray(carry, dtype)


def _validate(problem: Problem, dtype, v_dtype, carry, k: int,
              c2tau2_field=None, compute_errors: bool = True,
              phase: float = oracle.TWO_PI):
    if k < 2:
        raise ValueError(f"k must be >= 2 (got {k}); use "
                         "leapfrog.solve_compensated for k=1")
    if problem.N % k:
        raise ValueError(f"k={k} must divide N={problem.N}")
    if c2tau2_field is not None and compute_errors:
        raise ValueError(
            "variable-c runs have no analytic oracle; pass "
            "compute_errors=False with c2tau2_field"
        )
    if c2tau2_field is not None and phase != oracle.TWO_PI:
        raise ValueError(
            "a shifted phase bootstraps layers 0/1 from the analytic "
            "solution, which only exists for constant speed; use the "
            "reference phase with c2tau2_field"
        )
    if dtype == jnp.bfloat16:
        raise ValueError(
            "compensated/velocity scheme requires an f32/f64 carrier u "
            "(bf16 representation error dominates; use v_dtype=bfloat16 "
            "for the increment-form bf16 mode)"
        )
    if v_dtype != dtype and carry:
        raise ValueError(
            "carry compensation requires v_dtype == dtype (a narrowed "
            "increment stream quantizes far above what the carry "
            "recovers); pass carry=False"
        )


def _rel_guard_tol(f):
    """|sx| threshold below which a plane counts as an analytic zero for
    the REL metric (see the guard comment in `_make_march`)."""
    return 512 * jnp.finfo(f).eps


def _error_fn_guarded(problem: Problem, dtype,
                      phase: float = oracle.TWO_PI):
    """Layer-error fn with the representation-zero sx planes excluded,
    so the bootstrap layer's metric matches the in-kernel layers'.

    (The excluded plane's ABS contribution is ~1e-16 * |syz| - far below
    any solver error - so abs is unchanged in practice.)"""
    f_dtype = stencil_ref.compute_dtype(dtype)
    sx, sy, sz = oracle.spatial_factors(problem, f_dtype)
    ct_table = oracle.time_factor_table(problem, f_dtype, phase)
    mask = jnp.asarray(oracle.interior_masks_1d(problem.N))
    mask_x = mask & (jnp.abs(sx) > _rel_guard_tol(f_dtype))

    def errors(u, n):
        f = oracle.analytic_field(sx, sy, sz, ct_table[n])
        return oracle.layer_errors(u.astype(f_dtype), f, mask_x, mask, mask)

    return errors


def _make_march(problem, dtype, v_dtype, carry_on, k, compute_errors,
                block_x, interpret, nsteps, has_field=False,
                chunk_len=None, phase: float = oracle.TWO_PI):
    """Shared march: k-fused blocks + a k=1 tail through the SAME kernel.

    Returns `march(u, v, carry, start, *field_params)` ->
    (u, v, carry, abs, rel) covering layers start+1..nsteps (`start` a
    Python int).  Shared by solve and resume so a resumed run's op
    sequence equals the uninterrupted run's.  With `chunk_len` set the
    march covers exactly chunk_len layers from a RUNTIME `start`
    (run/supervisor.py's cached chunk program); on block-aligned starts
    the op sequence equals the uninterrupted march's prefix.  With
    `has_field` the c^2tau^2 field rides `field_params[0]` as a runtime
    argument (leapfrog.ParamStep reasoning) into every onion call.
    """
    f = stencil_ref.compute_dtype(dtype)
    sx, ct, syz, rsyz, xmask, inv_absx = kfused._oracle_parts(
        problem, f, phase
    )
    # Rel-metric guard: exclude REPRESENTATION-LEVEL zeros of the periodic
    # x factor (sin at the domain midpoint evaluates to ~1.2e-16, not 0,
    # so the exact-zero NaN-skip of the reference contract misses it and
    # 1/|sx| reaches ~8e15).  On bitwise-antisymmetric trajectories (all
    # 1-step paths, the standard onion) that plane's noise stays
    # proportional and the metric quietly reports a noise/noise ratio
    # (~0.22 at N=32 - it dominates the reported rel of EVERY path,
    # including the reference's own metric, mpi_new.cpp:340-344).  The
    # velocity-form onion's zero-seeded halo carries break the antisymmetry
    # by ~2e-9 absolute, which 8e15 would amplify into 1e7 garbage; this
    # path therefore applies the NaN-skip at representation level, where
    # it belongs.  Abs errors are untouched.  Honest min over real modes:
    # |sx| >= sin(2*pi/N), e.g. 0.012 at N=512 >> tol for any f32 run.
    inv_absx = jnp.where(jnp.abs(sx) > _rel_guard_tol(f), inv_absx,
                         jnp.asarray(0.0, f))

    def kblock(u, v, carry, nstart, kk, bxo, field=None):
        ctk = lax.dynamic_slice(ct, (nstart + 1,), (kk,))
        sxct = ctk[:, None] * sx[None, :]
        u2, v2, c2, dmax, rmax = stencil_pallas.fused_kstep_comp(
            u, v, carry, syz, rsyz, sxct,
            k=kk, coeff=problem.a2tau2, inv_h2=problem.inv_h2,
            c2tau2_field=field,
            block_x=bxo, interpret=interpret, with_errors=compute_errors,
        )
        if compute_errors:
            abs_e, rel_e = kfused._block_errors(
                dmax, rmax, ctk, xmask, inv_absx
            )
        else:
            abs_e = rel_e = jnp.zeros((kk,), f)
        return u2, v2, c2, abs_e, rel_e

    def march(u, v, carry, start, *field_params):
        field = field_params[0] if has_field else None
        if chunk_len is None:
            nblocks = (nsteps - start) // k
            rem = (nsteps - start) - nblocks * k
        else:
            nblocks = chunk_len // k
            rem = chunk_len - nblocks * k

        def body(state, nstart):
            u, v, carry = state
            u2, v2, c2, abs_e, rel_e = kblock(
                u, v, carry, nstart, k, block_x, field
            )
            return (u2, v2, c2), (abs_e, rel_e)

        starts = start + k * jnp.arange(nblocks)
        # Two blocks a loop turn, so that XLA copies no state field before
        # each call: see kfused._make_march.
        (u, v, carry), (abs_b, rel_b) = lax.scan(
            body, (u, v, carry), starts, unroll=2
        )
        abs_parts = [abs_b.reshape(-1)]
        rel_parts = [rel_b.reshape(-1)]
        for t in range(rem):
            rem_start = (
                nsteps - rem if chunk_len is None
                else start + chunk_len - rem
            )
            u, v, carry, abs_1, rel_1 = kblock(
                u, v, carry, rem_start + t, 1, None, field
            )
            abs_parts.append(abs_1)
            rel_parts.append(rel_1)
        return u, v, carry, jnp.concatenate(abs_parts), jnp.concatenate(
            rel_parts)

    return march


def _bootstrap(problem, dtype, v_dtype, carry_on, carry_dtype, interpret,
               field=None, phase: float = oracle.TWO_PI):
    """Layers 0/1: analytic init + the compensated kernel's half-step.

    u1 = u0 + (C/2)lap(u0) with v = carry = 0 primes (u1, v1, carry1)
    exactly as `leapfrog.make_compensated_solver` (reference bootstrap:
    openmp_sol.cpp:123-145).  With a `field` the half-step coefficient is
    tau^2 c^2(x)/2 and the k=1 onion kernel runs it (op-for-op the same
    Kahan sequence, with the field as the Laplacian coefficient).

    A shifted `phase` (constant speed only - _validate) takes the exact
    analytic two-level initialization instead: u0/u1 analytic, v1 the
    exact analytic increment (leapfrog.analytic_increment_layer1, a
    pure product - never u1 - u0, whose FMA contraction drifts between
    program shapes), zero Kahan carry - the leapfrog analytic bootstrap
    with the onion's storage dtypes."""
    if phase != oracle.TWO_PI:
        u1 = leapfrog.analytic_layer(problem, dtype, phase, 1)
        v1 = leapfrog.analytic_increment_layer1(problem, v_dtype, phase)
        c1 = (
            jnp.zeros(u1.shape, carry_dtype) if carry_on else None
        )
        return u1, v1, c1
    u0 = leapfrog.initial_layer0(problem, dtype)
    if field is None:
        zero = jnp.zeros_like(u0)
        u1, v1, c1 = stencil_pallas.compensated_step(
            u0, zero, zero, problem, 0.5 * problem.a2tau2,
            interpret=interpret
        )
        v1 = v1.astype(v_dtype)
        c1 = c1.astype(carry_dtype) if carry_on else None
        return u1, v1, c1
    f = stencil_ref.compute_dtype(dtype)
    n = problem.N
    zero_plane = jnp.zeros((n, n), f)
    u1, v1, c1, _, _ = stencil_pallas.fused_kstep_comp(
        u0, jnp.zeros(u0.shape, v_dtype),
        jnp.zeros(u0.shape, carry_dtype) if carry_on else None,
        zero_plane, zero_plane, jnp.zeros((1, n), f),
        k=1, coeff=None, inv_h2=problem.inv_h2,
        c2tau2_field=0.5 * field, interpret=interpret, with_errors=False,
    )
    return u1, v1, c1


def make_kfused_comp_solver(
    problem: Problem,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    block_x: Optional[int] = None,
    interpret: bool = False,
    v_dtype=None,
    carry: bool = True,
    carry_dtype=None,
    c2tau2_field=None,
    phase: float = oracle.TWO_PI,
):
    """Build the jitted compensated k-fused solver; returns
    `(runner, run_params)` yielding (u, v, carry|None, abs_errors,
    rel_errors).  `run_params` is () for constant speed (zero-arg runner,
    as before) or the materialized device field for variable c (a runtime
    argument, never an HLO literal - leapfrog.ParamStep).

    `carry_dtype` (default: `_default_carry_dtype`, i.e. bf16 for f32
    runs) narrows only the carry's HBM stream - see that helper for the
    numerics and the measured +6%.  `phase` is the lane identity of the
    ensemble engine (analytic two-level bootstrap when shifted; constant
    speed only - see `_bootstrap`).
    """
    v_dtype = dtype if v_dtype is None else jnp.dtype(v_dtype)
    carry_dtype = (
        _default_carry_dtype(dtype) if carry_dtype is None
        else jnp.dtype(carry_dtype)
    )
    if carry:
        _validate_carry_dtype(dtype, carry_dtype)
    _validate(problem, dtype, v_dtype, carry, k, c2tau2_field,
              compute_errors, phase)
    nsteps = problem.timesteps if stop_step is None else stop_step
    if not 1 <= nsteps <= problem.timesteps:
        raise ValueError(
            f"stop_step must be in [1, {problem.timesteps}], got {nsteps}"
        )
    f = stencil_ref.compute_dtype(dtype)
    has_field = c2tau2_field is not None
    errors = _error_fn_guarded(problem, dtype, phase)
    march = _make_march(
        problem, dtype, v_dtype, carry, k, compute_errors, block_x,
        interpret, nsteps, has_field, phase=phase,
    )

    def run(*field_params):
        u1, v1, c1 = _bootstrap(
            problem, dtype, v_dtype, carry, carry_dtype, interpret,
            field_params[0] if has_field else None, phase,
        )
        a0 = r0 = jnp.zeros((), f)
        if compute_errors:
            a1, r1 = errors(u1, 1)
        else:
            a1 = r1 = jnp.zeros((), f)
        u, v, c, abs_t, rel_t = march(u1, v1, c1, 1, *field_params)
        abs_all = jnp.concatenate([jnp.stack([a0, a1]), abs_t])
        rel_all = jnp.concatenate([jnp.stack([r0, r1]), rel_t])
        return u, v, c, abs_all, rel_all

    run_params = ()
    if has_field:
        run_params = (leapfrog.ParamStep.materialize(
            jnp.asarray(c2tau2_field, dtype=f)
        ),)
    return jax.jit(run), run_params


def _as_result(problem, out, init_s, solve_s, steps_computed, final_step):
    u, v, c, abs_all, rel_all = out
    f = stencil_ref.compute_dtype(u.dtype)
    return leapfrog.SolveResult(
        problem=problem,
        u_prev=(u.astype(f) - v.astype(f)).astype(u.dtype),
        u_cur=u,
        abs_errors=np.asarray(abs_all, dtype=np.float64),
        rel_errors=np.asarray(rel_all, dtype=np.float64),
        init_seconds=init_s,
        solve_seconds=solve_s,
        steps_computed=steps_computed,
        final_step=final_step,
        comp_v=v,
        comp_carry=c,
    )


def solve_kfused_comp(
    problem: Problem,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    block_x: Optional[int] = None,
    interpret: bool = False,
    v_dtype=None,
    carry: bool = True,
    carry_dtype=None,
    c2tau2_field=None,
    phase: float = oracle.TWO_PI,
) -> leapfrog.SolveResult:
    """Compile + run the compensated k-fused solve (reference timing
    phases as `leapfrog.solve`).  `c2tau2_field` selects the variable-c
    velocity-form onion (composes with the carry and the bf16-increment
    mode); pair it with compute_errors=False.  `phase` shifts the
    analytic initial condition (constant speed only)."""
    runner, run_params = make_kfused_comp_solver(
        problem, dtype, k, compute_errors, stop_step, block_x, interpret,
        v_dtype, carry, carry_dtype, c2tau2_field, phase,
    )
    out, init_s, solve_s = leapfrog._timed_compile_run(
        runner, run_params, sync=lambda o: np.asarray(o[3]),
        path="kfused_comp", scheme="compensated", k=k, n=problem.N,
    )
    with tracing.span("solve.finish", path="kfused_comp"):
        result = _as_result(
            problem, out, init_s, solve_s, stop_step,
            stop_step if stop_step is not None else problem.timesteps,
        )
        obs_metrics.record_solve(
            result, "kfused_comp", scheme="compensated", k=k,
            v_itemsize=(
                None if v_dtype is None else jnp.dtype(v_dtype).itemsize
            ),
            carry=carry, with_field=c2tau2_field is not None,
            block_x=block_x,
        )
    return result


def _validate_sharded(problem: Problem, dtype, v_dtype, carry, k, n_x,
                      n_y: int = 1, c2tau2_field=None,
                      compute_errors: bool = True):
    _validate(problem, dtype, v_dtype, carry, k, c2tau2_field,
              compute_errors)
    if n_x < 1 or n_y < 1:
        raise ValueError(
            f"mesh axes must be >= 1 (got MX={n_x}, MY={n_y})"
        )
    if problem.N % n_x:
        raise ValueError(
            f"sharded compensated k-fusion needs N % shards == 0 "
            f"(N={problem.N}, shards={n_x})"
        )
    if (problem.N // n_x) % k:
        raise ValueError(
            f"k={k} must divide the shard depth {problem.N // n_x}"
        )
    if problem.N % n_y:
        raise ValueError(
            f"y-sharded compensated k-fusion needs N % y-shards == 0 "
            f"(N={problem.N}, y-shards={n_y})"
        )
    if problem.N // n_y < k:
        raise ValueError(
            f"k={k} exceeds the y shard depth {problem.N // n_y}"
        )


def _make_sharded_runner(problem, mesh, grid, dtype, v_dtype, carry_on, k,
                         compute_errors, nsteps, start_step, block_x,
                         interpret, carry_dtype=None, has_field=False,
                         chunk_len=None):
    """Sharded velocity-form runner over (MX, MY, 1): the distributed
    flagship.

    One cyclic k-deep ppermute pair per mesh axis per field (u, v) per
    k-block; on 2D grids the y-row extension happens FIRST and the x
    ghost planes are sliced from the extended blocks (the corner
    sequencing of solver/sharded_kfused.py).  The carry stays
    shard-local with zero-seeded halos exactly as on a single device.
    y-sharding shrinks every VMEM plane by MY - which is what lifts the
    VMEM bound on k (x-only at N=512 is k<=2; (8,8,1) runs k=4).  The
    bootstrap and the remainder tail run the same kernel at k=1 (the
    bootstrap with coeff C/2 on zero v/carry IS the compensated
    half-step).

    With `has_field` the c^2tau^2 field rides as an extra P("x","y")
    runtime argument; it is time-invariant, so its y extension and
    x-ghost exchange happen ONCE per solve per needed ghost depth
    (k-blocks; k=1 for bootstrap/remainder), outside the layer scan.

    With `chunk_len` set (start_step must be None) the runner is the
    supervised chunk program `run(u, v, carry, start, ...)`: exactly
    chunk_len layers from a RUNTIME start, one compiled program reused
    across every chunk (run/supervisor.py).
    """
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    n_x, n_y = grid
    if carry_dtype is None:
        carry_dtype = _default_carry_dtype(dtype)
    f = stencil_ref.compute_dtype(dtype)
    nl = problem.N // n_x
    nl_y = problem.N // n_y
    sx, ct, syz, rsyz, xmask, inv_absx = kfused._oracle_parts(problem, f)
    inv_absx = jnp.where(jnp.abs(sx) > _rel_guard_tol(f), inv_absx,
                         jnp.asarray(0.0, f))
    sxct_all = ct[:, None] * sx[None, :]
    perm_fwd = [(i, (i + 1) % n_x) for i in range(n_x)]
    perm_bwd = [(i, (i - 1) % n_x) for i in range(n_x)]
    perm_fwd_y = [(i, (i + 1) % n_y) for i in range(n_y)]
    perm_bwd_y = [(i, (i - 1) % n_y) for i in range(n_y)]
    if chunk_len is None:
        start = 1 if start_step is None else start_step
        nblocks = (nsteps - start) // k
        rem = (nsteps - start) - nblocks * k
    else:
        nblocks = chunk_len // k
        rem = chunk_len - nblocks * k
    # One block_x for every kk so the op sequence matches the
    # single-device kernel's block partitioning (bitwise contract).
    itemsizes = (
        jnp.dtype(dtype).itemsize, jnp.dtype(v_dtype).itemsize,
        jnp.dtype(carry_dtype).itemsize if carry_on else None,
    )
    if n_y == 1:
        bx = block_x or stencil_pallas.choose_kstep_comp_block(
            problem.N, k, *itemsizes, depth=nl, ghosts=True,
            field=has_field,
        )
    else:
        bx = block_x or stencil_pallas.choose_kstep_comp_block(
            problem.N, k, *itemsizes, depth=nl, ghosts=True,
            plane_elems=(nl_y + 2 * k) * problem.N, field=has_field,
        )
    if bx is None:
        raise ValueError(
            f"k={k} does not fit VMEM for N={problem.N} over "
            f"({n_x}, {n_y}, 1) shards"
        )

    def ghosts(a, kk):
        if n_x == 1:
            return a[-kk:], a[:kk]
        return (
            lax.ppermute(a[-kk:], "x", perm_fwd),
            lax.ppermute(a[:kk], "x", perm_bwd),
        )

    def extend_y(a, kk):
        # Only called on the n_y > 1 path (kcall dispatches the x-only
        # kernel otherwise, matching solver/sharded_kfused.py).
        lo = lax.ppermute(a[:, -kk:], "y", perm_fwd_y)
        hi = lax.ppermute(a[:, :kk], "y", perm_bwd_y)
        return jnp.concatenate([lo, a, hi], axis=1)

    def field_pack(fld, kk):
        """(block_or_ext, x-ghost pair) for the time-invariant field at
        ghost depth kk - built once per solve per needed depth."""
        if fld is None:
            return None
        if n_y == 1:
            return fld, ghosts(fld, kk)
        fe = extend_y(fld, kk)
        return fe, ghosts(fe, kk)

    def kcall(syz_c, rsyz_c, u, v, c, sxct_k, kk, coeff, with_err,
              fp=None):
        c2b = fp[0] if fp is not None else None
        c2g = fp[1] if fp is not None else None
        if n_y == 1:
            return stencil_pallas.fused_kstep_comp_sharded(
                u, v, c, ghosts(u, kk), ghosts(v, kk), syz_c, rsyz_c,
                sxct_k, k=kk, coeff=coeff, inv_h2=problem.inv_h2,
                c2tau2_block=c2b, c2_ghosts=c2g,
                block_x=bx, interpret=interpret, with_errors=with_err,
            )
        ue, ve = extend_y(u, kk), extend_y(v, kk)
        y0 = lax.axis_index("y") * nl_y
        u2, v2, c2, dm, rm = stencil_pallas.fused_kstep_comp_sharded_xy(
            ue, ve, c, ghosts(ue, kk), ghosts(ve, kk), syz_c, rsyz_c,
            sxct_k, y0, problem.N, k=kk, nl_y=nl_y, coeff=coeff,
            inv_h2=problem.inv_h2, c2tau2_ext=c2b, c2_ghosts=c2g,
            block_x=bx, interpret=interpret,
            with_errors=with_err,
        )
        if with_err:
            dm = lax.pmax(dm, "y")
            rm = lax.pmax(rm, "y")
        return u2, v2, c2, dm, rm

    def layer_rows(syz_c, rsyz_c, u, sxct_row):
        d, r = kfused._layer_rows_local(u, sxct_row, syz_c, rsyz_c, f)
        if n_y > 1:
            d = lax.pmax(d, "y")
            r = lax.pmax(r, "y")
        return d, r

    def local_march(syz_c, rsyz_c, u, v, c, sxct_loc, first, fld=None):
        rows_d, rows_r = [], []
        fp_k = field_pack(fld, k)
        fp_1 = field_pack(fld, 1) if rem else None

        def body(state, nstart):
            u, v, c = state
            sxct_k = lax.dynamic_slice(sxct_loc, (nstart + 1, 0), (k, nl))
            u2, v2, c2, dm, rm = kcall(
                syz_c, rsyz_c, u, v, c, sxct_k, k, problem.a2tau2,
                compute_errors, fp_k,
            )
            if not compute_errors:
                dm = rm = jnp.zeros((k, nl), f)
            return (u2, v2, c2), (dm, rm)

        starts = first + k * jnp.arange(nblocks)
        (u, v, c), (dmb, rmb) = lax.scan(body, (u, v, c), starts)
        rows_d.append(dmb.reshape(-1, nl))
        rows_r.append(rmb.reshape(-1, nl))
        for t in range(rem):
            # == nsteps - rem + 1 + t on the full march; phrasing it off
            # `first` keeps the identical arithmetic valid for a traced
            # chunk start.
            layer = jnp.asarray(first + nblocks * k + 1 + t, jnp.int32)
            sxct_1 = lax.dynamic_slice(
                sxct_loc, (layer, jnp.int32(0)), (1, nl)
            )
            u, v, c, dm, rm = kcall(
                syz_c, rsyz_c, u, v, c, sxct_1, 1, problem.a2tau2,
                compute_errors, fp_1,
            )
            if not compute_errors:
                dm = rm = jnp.zeros((1, nl), f)
            rows_d.append(dm)
            rows_r.append(rm)
        return u, v, c, jnp.concatenate(rows_d), jnp.concatenate(rows_r)

    def assemble(dmax, rmax):
        if not compute_errors:
            z = jnp.zeros((nsteps + 1,), f)
            return z, z
        return kfused._block_errors(
            dmax, rmax, ct[: dmax.shape[0]], xmask, inv_absx
        )

    state_spec = P("x", "y")
    rows_spec = P(None, "x")
    plane_spec = P("y", None)

    field_specs = (state_spec,) if has_field else ()

    if chunk_len is not None:
        assert start_step is None

        def local_chunk(u, v, c, start, sxct_loc, syz_c, rsyz_c, *fargs):
            return local_march(
                syz_c, rsyz_c, u, v, c, sxct_loc, start,
                fargs[0] if has_field else None,
            )

        local_fn = jax.shard_map(
            local_chunk, mesh=mesh,
            in_specs=(state_spec, state_spec,
                      state_spec if carry_on else None,
                      P(), rows_spec, plane_spec, plane_spec)
            + field_specs,
            out_specs=(state_spec, state_spec,
                       state_spec if carry_on else None,
                       rows_spec, rows_spec),
            check_vma=False,
        )

        def run_chunk(u, v, c, start, *fargs):
            u, v, c, dmax, rmax = local_fn(
                u, v, c, start, sxct_all, syz, rsyz, *fargs
            )
            if compute_errors:
                ctk = lax.dynamic_slice(ct, (start + 1,), (chunk_len,))
                abs_e, rel_e = kfused._block_errors(
                    dmax, rmax, ctk, xmask, inv_absx
                )
            else:
                abs_e = rel_e = jnp.zeros((chunk_len,), f)
            return u, v, c, abs_e, rel_e

        return jax.jit(run_chunk)

    if start_step is None:

        def local(u0, sxct_loc, syz_c, rsyz_c, *fargs):
            fld = fargs[0] if has_field else None
            zero_v = jnp.zeros(u0.shape, v_dtype)
            zero_c = (
                jnp.zeros(u0.shape, carry_dtype) if carry_on else None
            )
            u1, v1, c1, _, _ = kcall(
                syz_c, rsyz_c, u0, zero_v, zero_c,
                jnp.zeros((1, nl), f), 1, 0.5 * problem.a2tau2, False,
                field_pack(0.5 * fld, 1) if has_field else None,
            )
            if compute_errors:
                d1, r1 = layer_rows(syz_c, rsyz_c, u1, sxct_loc[1])
            else:
                d1 = r1 = jnp.zeros((1, nl), f)
            u, v, c, rows_d, rows_r = local_march(
                syz_c, rsyz_c, u1, v1, c1, sxct_loc, 1, fld
            )
            zero = jnp.zeros((1, nl), f)
            return (
                u, v, c,
                jnp.concatenate([zero, d1, rows_d]),
                jnp.concatenate([zero, r1, rows_r]),
            )

        local_fn = jax.shard_map(
            local, mesh=mesh,
            in_specs=(state_spec, rows_spec, plane_spec, plane_spec)
            + field_specs,
            out_specs=(state_spec, state_spec,
                       state_spec if carry_on else None,
                       rows_spec, rows_spec),
            check_vma=False,
        )

        def run(*fargs):
            u0 = lax.with_sharding_constraint(
                leapfrog.initial_layer0(problem, dtype),
                NamedSharding(mesh, state_spec),
            )
            u, v, c, dmax, rmax = local_fn(
                u0, sxct_all, syz, rsyz, *fargs
            )
            abs_e, rel_e = assemble(dmax, rmax)
            return u, v, c, abs_e, rel_e

        return jax.jit(run)

    def local_resume(u, v, c, sxct_loc, syz_c, rsyz_c, *fargs):
        u, v, c, rows_d, rows_r = local_march(
            syz_c, rsyz_c, u, v, c, sxct_loc, start_step,
            fargs[0] if has_field else None,
        )
        head = jnp.zeros((start_step + 1, nl), f)
        return (
            u, v, c,
            jnp.concatenate([head, rows_d]),
            jnp.concatenate([head, rows_r]),
        )

    local_fn = jax.shard_map(
        local_resume, mesh=mesh,
        in_specs=(state_spec, state_spec,
                  state_spec if carry_on else None,
                  rows_spec, plane_spec, plane_spec) + field_specs,
        out_specs=(state_spec, state_spec,
                   state_spec if carry_on else None,
                   rows_spec, rows_spec),
        check_vma=False,
    )

    def run(u, v, c, *fargs):
        u, v, c, dmax, rmax = local_fn(u, v, c, sxct_all, syz, rsyz,
                                       *fargs)
        abs_e, rel_e = assemble(dmax, rmax)
        return u, v, c, abs_e, rel_e

    return jax.jit(run)


def solve_kfused_comp_sharded(
    problem: Problem,
    n_shards: Optional[int] = None,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    block_x: Optional[int] = None,
    interpret: Optional[bool] = None,
    devices=None,
    v_dtype=None,
    carry: bool = True,
    mesh_shape=None,
    carry_dtype=None,
    c2tau2_field=None,
) -> leapfrog.SolveResult:
    """Distributed velocity-form compensated k-fused solve over an
    (MX, MY, 1) mesh - the flagship scheme at the reference's
    distributed scale (mpi_new.cpp's role), with the compensated
    accuracy contract.  `n_shards` is the x-only shorthand.  Requires
    MX | N, k | N/MX, MY | N, k <= N/MY.  `carry_dtype` as
    `solve_kfused_comp`; `c2tau2_field` threads the variable-c field
    through the sharded onion (compute_errors=False required) - the c^2
    slab is sharded on the same mesh with its ghost exchange hoisted out
    of the layer scan (the field is time-invariant)."""
    from wavetpu.core.grid import build_mesh
    from wavetpu.solver.sharded_kfused import _resolve_grid

    if devices is None:
        devices = jax.devices()
    n_x, n_y = _resolve_grid(mesh_shape, n_shards, devices)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    v_dtype = dtype if v_dtype is None else jnp.dtype(v_dtype)
    if carry and carry_dtype is not None:
        _validate_carry_dtype(dtype, carry_dtype)
    _validate_sharded(problem, dtype, v_dtype, carry, k, n_x, n_y,
                      c2tau2_field, compute_errors)
    nsteps = problem.timesteps if stop_step is None else stop_step
    if not 1 <= nsteps <= problem.timesteps:
        raise ValueError(
            f"stop_step must be in [1, {problem.timesteps}], got {nsteps}"
        )
    mesh = build_mesh((n_x, n_y, 1), devices[: n_x * n_y])
    has_field = c2tau2_field is not None
    runner = _make_sharded_runner(
        problem, mesh, (n_x, n_y), dtype, v_dtype, carry, k,
        compute_errors, nsteps, None, block_x, interpret,
        carry_dtype=carry_dtype, has_field=has_field,
    )
    run_params = ()
    if has_field:
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        f = stencil_ref.compute_dtype(dtype)
        run_params = (jax.device_put(
            jnp.asarray(c2tau2_field, dtype=f),
            NamedSharding(mesh, P("x", "y")),
        ),)
    out, init_s, solve_s = leapfrog._timed_compile_run(
        runner, run_params, sync=lambda o: np.asarray(o[3]),
        path="kfused_comp_sharded", scheme="compensated", k=k, n=problem.N,
    )
    with tracing.span("solve.finish", path="kfused_comp_sharded"):
        result = _as_result(
            problem, out, init_s, solve_s, stop_step,
            stop_step if stop_step is not None else problem.timesteps,
        )
        obs_metrics.record_solve(
            result, "kfused_comp_sharded", scheme="compensated", k=k,
            v_itemsize=(
                None if v_dtype is None else jnp.dtype(v_dtype).itemsize
            ),
            carry=carry, with_field=c2tau2_field is not None,
            block_x=block_x,
            # Same depth/ghosts arguments the sharded chooser above used,
            # so the roofline model reads the block the kernel runs.
            depth=problem.N // n_x, ghosts=True,
        )
    return result


def resume_kfused_comp_sharded(
    problem: Problem,
    u_cur,
    v,
    carry,
    start_step: int,
    n_shards: Optional[int] = None,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    block_x: Optional[int] = None,
    interpret: Optional[bool] = None,
    devices=None,
    v_dtype=None,
    mesh_shape=None,
    c2tau2_field=None,
) -> leapfrog.SolveResult:
    """Re-enter the sharded velocity-form march at layer `start_step`
    from compensated checkpoint state (carry=None resumes the carry-less
    increment form).  A variable-c checkpoint resumes under the same
    re-passed `c2tau2_field`."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from wavetpu.core.grid import build_mesh
    from wavetpu.solver.sharded_kfused import _resolve_grid

    if devices is None:
        devices = jax.devices()
    n_x, n_y = _resolve_grid(mesh_shape, n_shards, devices)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    v_dtype = dtype if v_dtype is None else jnp.dtype(v_dtype)
    carry_on = carry is not None
    _validate_sharded(problem, dtype, v_dtype, carry_on, k, n_x, n_y,
                      c2tau2_field, compute_errors)
    nsteps = problem.timesteps
    if not 1 <= start_step <= nsteps:
        raise ValueError(
            f"start_step must be in [1, {nsteps}], got {start_step}"
        )
    mesh = build_mesh((n_x, n_y, 1), devices[: n_x * n_y])
    if carry_on:
        # No-copy dtype probe + the same preserve-or-cast rule as
        # resume_kfused_comp.
        carry = _normalize_carry(carry, dtype)
    has_field = c2tau2_field is not None
    runner = _make_sharded_runner(
        problem, mesh, (n_x, n_y), dtype, v_dtype, carry_on, k,
        compute_errors, nsteps, start_step, block_x, interpret,
        carry_dtype=jnp.result_type(carry) if carry_on else None,
        has_field=has_field,
    )
    sharding = NamedSharding(mesh, P("x", "y"))
    args = (
        jax.device_put(jnp.asarray(u_cur, dtype), sharding),
        jax.device_put(jnp.asarray(v, v_dtype), sharding),
        jax.device_put(carry, sharding) if carry_on else None,
    )
    if has_field:
        f = stencil_ref.compute_dtype(dtype)
        args = args + (jax.device_put(
            jnp.asarray(c2tau2_field, dtype=f), sharding
        ),)
    out, init_s, solve_s = leapfrog._timed_compile_run(
        runner, args, sync=lambda o: np.asarray(o[3]),
        path="kfused_comp_sharded", scheme="compensated", k=k, n=problem.N,
    )
    return _as_result(
        problem, out, init_s, solve_s, nsteps - start_step, nsteps
    )


def resume_kfused_comp(
    problem: Problem,
    u_cur,
    v,
    carry,
    start_step: int,
    dtype=jnp.float32,
    k: int = 4,
    compute_errors: bool = True,
    block_x: Optional[int] = None,
    interpret: bool = False,
    v_dtype=None,
    c2tau2_field=None,
) -> leapfrog.SolveResult:
    """Re-enter the compensated k-fused march at layer `start_step`.

    `(u_cur, v, carry)` is the compensated checkpoint state
    (SolveResult.u_cur / .comp_v / .comp_carry); `carry=None` resumes the
    carry-less increment form.  The march is the same op sequence as an
    uninterrupted run's from that layer, so a same-path resume is
    self-consistent; a cross-path resume (1-step compensated <-> k-fused)
    agrees to scheme tolerance, not bitwise.  A variable-c checkpoint
    resumes under the same re-passed `c2tau2_field` (checkpoints store
    state, not the field).
    """
    v_dtype = dtype if v_dtype is None else jnp.dtype(v_dtype)
    carry_on = carry is not None
    _validate(problem, dtype, v_dtype, carry_on, k, c2tau2_field,
              compute_errors)
    nsteps = problem.timesteps
    if not 1 <= start_step <= nsteps:
        raise ValueError(
            f"start_step must be in [1, {nsteps}], got {start_step}"
        )
    f = stencil_ref.compute_dtype(dtype)
    has_field = c2tau2_field is not None
    march = _make_march(
        problem, dtype, v_dtype, carry_on, k, compute_errors, block_x,
        interpret, nsteps, has_field,
    )

    def run(u_cur, v, carry, *field_params):
        u, vv, cc, abs_t, rel_t = march(
            u_cur, v, carry, start_step, *field_params
        )
        head = jnp.zeros((start_step + 1,), f)
        return (
            u, vv, cc,
            jnp.concatenate([head, abs_t]),
            jnp.concatenate([head, rel_t]),
        )

    args = (
        jnp.asarray(u_cur, dtype),
        jnp.asarray(v, v_dtype),
        # Preserve a valid stored carry dtype (bf16-carry checkpoints
        # resume bitwise; legacy f32 carries stay f32); invalid combos
        # (e.g. f64 carry into an f32 run) cast to the state dtype.
        _normalize_carry(carry, dtype) if carry_on else None,
    )
    if has_field:
        args = args + (leapfrog.ParamStep.materialize(
            jnp.asarray(c2tau2_field, dtype=f)
        ),)
    out, init_s, solve_s = leapfrog._timed_compile_run(
        jax.jit(run), args, sync=lambda o: np.asarray(o[3]),
        path="kfused_comp", scheme="compensated", k=k, n=problem.N,
    )
    return _as_result(
        problem, out, init_s, solve_s, nsteps - start_step, nsteps
    )


def make_chunk_runner(
    problem: Problem,
    dtype=jnp.float32,
    length: int = 4,
    k: int = 4,
    compute_errors: bool = True,
    block_x: Optional[int] = None,
    interpret: bool = False,
    v_dtype=None,
    carry: bool = True,
    c2tau2_field=None,
):
    """Fixed-length compensated k-fused re-entry for supervised solves.

    Returns `(runner, run_params)`; `runner(u, v, carry, start,
    *run_params)` (carry=None resumes the carry-less increment form)
    marches layers start+1..start+length with a RUNTIME `start` - one
    compiled program per chunk length (run/supervisor.py).  On
    block-aligned starts with length a multiple of k the op sequence
    equals the uninterrupted march's prefix, so supervision preserves
    the velocity-form onion's exact trajectory.
    """
    v_dtype = dtype if v_dtype is None else jnp.dtype(v_dtype)
    _validate(problem, dtype, v_dtype, carry, k, c2tau2_field,
              compute_errors)
    if length < 1:
        raise ValueError(f"chunk length must be >= 1, got {length}")
    f = stencil_ref.compute_dtype(dtype)
    has_field = c2tau2_field is not None
    march = _make_march(
        problem, dtype, v_dtype, carry, k, compute_errors, block_x,
        interpret, None, has_field, chunk_len=length,
    )

    def run(u_cur, v, carry, start, *field_params):
        return march(u_cur, v, carry, start, *field_params)

    run_params = ()
    if has_field:
        run_params = (leapfrog.ParamStep.materialize(
            jnp.asarray(c2tau2_field, dtype=f)
        ),)
    return jax.jit(run), run_params


def make_sharded_chunk_runner(
    problem: Problem,
    mesh,
    grid,
    dtype=jnp.float32,
    length: int = 4,
    k: int = 4,
    compute_errors: bool = True,
    block_x: Optional[int] = None,
    interpret: Optional[bool] = None,
    v_dtype=None,
    carry: bool = True,
    carry_dtype=None,
    has_field: bool = False,
):
    """Sharded counterpart of `make_chunk_runner` over an (MX, MY, 1)
    mesh: `runner(u, v, carry, start[, field])` with all state P("x","y")
    on `mesh` and a RUNTIME `start` - the supervised chunk program for
    the distributed velocity-form flagship."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    v_dtype = dtype if v_dtype is None else jnp.dtype(v_dtype)
    _validate_sharded(problem, dtype, v_dtype, carry, k, grid[0], grid[1],
                      None, True)
    if length < 1:
        raise ValueError(f"chunk length must be >= 1, got {length}")
    return _make_sharded_runner(
        problem, mesh, grid, dtype, v_dtype, carry, k, compute_errors,
        None, None, block_x, interpret, carry_dtype=carry_dtype,
        has_field=has_field, chunk_len=length,
    )
