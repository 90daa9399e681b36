"""Distributed solver: one jitted `shard_map` program over a 3D device mesh.

The analog of the reference's MPI variants (mpi_new.cpp:324-372 fused loop,
mpi_sol.cpp:374-478 topology setup) redesigned for ICI: the whole solve -
layer-0/1 bootstrap, the time loop, halo exchange, boundary masking, and the
cross-device error max-reduction - is a single XLA computation per chip.
There is no host round-trip anywhere: halos ride `ppermute` (comm/halo.py)
and the per-layer L-inf errors are `lax.pmax`-reduced in-program (the
counterpart of the end-of-run MPI_Reduce(MPI_MAX), mpi_new.cpp:360-361).

The hot kernel is injectable, like `leapfrog.make_solver`'s `step_fn`:
`kernel="pallas"` runs the fused Pallas slab kernel on every shard - the
true analog of the reference's flagship binary, where each MPI rank drives
the CUDA kernel (cuda_sol.cpp:381-443 launching calculate_layer,
cuda_sol_kernels.cu:24-47); `kernel="roll"` keeps the pure-XLA
halo-extended stencil as the semantic reference.  `overlap=True` issues the
6 `ppermute`s with no data dependence on the bulk update so XLA's scheduler
can fly them during the stencil, then patches the 6 faces - the
compute/communication overlap the reference leaves on the table (its
exchange is fully serialized with the loop, mpi_new.cpp:327-352).

Sharding model (see core/grid.py): the fundamental (N, N, N) state is
zero-padded per axis to a multiple of the mesh dim and laid out
PartitionSpec("x", "y", "z").  All 1-D problem data (analytic factors, error
masks, boundary masks) is precomputed on host in f64, padded, and sharded
along its own axis, so every shard receives exactly its slice - the moral
equivalent of the reference's per-rank x_0/y_0/z_0 offsets
(mpi_sol.cpp:423-429) without any per-rank branching.  A variable-c field
(tau^2 c^2(x,y,z)) is padded the same way and rides through the program as
a runtime argument sharded P("x","y","z") - never a closed-over constant
(see solver.leapfrog.ParamStep for why).

bf16 state computes in f32 (stencil_ref.compute_dtype), matching the
single-device solver's bf16-storage / f32-accumulation contract.
"""

from __future__ import annotations

import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from wavetpu.comm import halo
from wavetpu.core.grid import AXIS_NAMES, Topology, build_mesh, choose_mesh_shape
from wavetpu.core.problem import Problem
from wavetpu.kernels import stencil_pallas, stencil_ref
from wavetpu.obs import metrics as obs_metrics
from wavetpu.solver.leapfrog import SolveResult
from wavetpu.verify import oracle


def _padded_factors(problem: Problem, topo: Topology, dtype):
    """Host-f64 1-D analytic factors on the padded per-axis grids.

    Pad cells get factor 0, so the padded analytic field vanishes there
    (consistent with the zero-padded state).  The factor formulas live in
    oracle.spatial_factors_np (single source of truth).
    """
    n = problem.N

    def pad(v, p):
        out = np.zeros(p, dtype=np.float64)
        out[:n] = v
        return out

    factors = oracle.spatial_factors_np(problem, n)
    return tuple(
        jnp.asarray(pad(v, p), dtype=dtype)
        for v, p in zip(factors, topo.padded)
    )


def _masks(problem: Problem, topo: Topology, dtype):
    """1-D boundary multipliers and error-interior masks, padded.

    bc (multiplied into every updated layer):
      x: 1 for real cells (global i < N) - the x=0 plane is a live periodic
         cell; 0 for pad cells.
      y/z: 0 at the stored Dirichlet plane (global 0) and pad cells
         (reference zeroes its y/z faces each step, openmp_sol.cpp:104-112).
    err (error reduction, reference interior = global 1..N-1 per axis,
         openmp_sol.cpp:174-176): global index != 0 and < N.

    The Pallas kernel reproduces exactly this bc predicate in-register
    from global offsets (the fused mask in stencil_pallas._sharded_kernel)
    so the two kernels stay interchangeable.
    """
    n = problem.N
    bc, err = [], []
    for axis, p in enumerate(topo.padded):
        g = np.arange(p)
        real = g < n
        if axis == 0:
            bc.append(real.astype(np.float64))
        else:
            bc.append((real & (g != 0)).astype(np.float64))
        err.append(real & (g != 0))
    bcs = tuple(jnp.asarray(b, dtype=dtype) for b in bc)
    errs = tuple(jnp.asarray(e) for e in err)
    return bcs, errs


def pad_field(field: np.ndarray, topo: Topology) -> np.ndarray:
    """Zero-pad an (N, N, N) host field to the topology's padded shape."""
    field = np.asarray(field)
    out = np.zeros(topo.padded, dtype=field.dtype)
    n = field.shape
    out[: n[0], : n[1], : n[2]] = field
    return out


def _shard_offsets(topo: Topology):
    """This shard's global cell offsets, int32 (3,) - must run inside
    shard_map.  The analog of the reference's per-rank x_0/y_0/z_0
    (mpi_sol.cpp:423-429)."""
    return jnp.stack(
        [
            lax.axis_index(name).astype(jnp.int32) * topo.block[axis]
            for axis, name in enumerate(AXIS_NAMES)
        ]
    )


def _self_ghosts(u):
    """The cyclic wrap planes of a block, shaped like collect_ghosts output.

    Feeding these to the sharded kernel makes it exactly periodic within the
    shard - the bulk update of overlap mode, and the correct ghosts for any
    axis whose mesh dim is 1.
    """
    ghosts = []
    for axis in range(3):
        b = u.shape[axis]
        lo = lax.slice_in_dim(u, b - 1, b, axis=axis)
        hi = lax.slice_in_dim(u, 0, 1, axis=axis)
        ghosts.append((lo, hi))
    return tuple(ghosts)


def _face_ext(u, ghosts, axis: int, p: int):
    """Halo-extended 3-plane slab around face plane `p` of `axis`.

    Returns a (3, by+2, bz+2)-shaped (axis-permuted) array whose interior
    `laplacian_ext` is the correct update stencil for the face plane,
    including its edge/corner cells: the out-of-block `axis` neighbour is
    the ghost plane, transverse neighbours come from the block itself, and
    the face plane's transverse *edges* come from the transverse ghosts
    (which `collect_ghosts` provides for every axis - local wrap slices on
    1-dim mesh axes).  Even shard splits only (overlap mode's contract).
    """
    b = u.shape[axis]
    glo, ghi = ghosts[axis]
    parts = []
    if p == 0:
        parts.append(glo)
    if b == 1:
        parts.append(u)
    else:
        lo = max(p - 1, 0)
        parts.append(lax.slice_in_dim(u, lo, min(p + 2, b), axis=axis))
    if p == b - 1:
        parts.append(ghi)
    core = jnp.concatenate(parts, axis)
    pads = [(1, 1)] * 3
    pads[axis] = (0, 0)
    ext = jnp.pad(core, pads)
    # Transverse ghost edges of the central (face) plane.
    for a in range(3):
        if a == axis:
            continue
        tlo, thi = ghosts[a]
        tlo = lax.slice_in_dim(tlo, p, p + 1, axis=axis)
        thi = lax.slice_in_dim(thi, p, p + 1, axis=axis)
        starts_lo = [0] * 3
        starts_hi = [0] * 3
        for d in range(3):
            if d == axis:
                starts_lo[d] = starts_hi[d] = 1  # central plane
            elif d == a:
                starts_lo[d] = 0
                starts_hi[d] = ext.shape[d] - 1
            else:
                starts_lo[d] = starts_hi[d] = 1
        ext = lax.dynamic_update_slice(ext, tlo, starts_lo)
        ext = lax.dynamic_update_slice(ext, thi, starts_hi)
    return ext


def _make_local_step(
    problem: Problem,
    topo: Topology,
    dtype,
    kernel: str,
    overlap: bool,
    interpret: bool,
    exchange: bool = True,
):
    """Build the per-shard step function `step(u_prev, u, bc, field)`.

    Returns the full leapfrog-form update u_next = 2u - u_prev + C*lap(u)
    with boundary/pad masking applied, where C is the scalar a2tau2 or the
    per-cell `field` block.  Runs inside shard_map.  The layer-1 bootstrap
    derives from this same function ((u0 + step(u0, u0))/2), so any kernel
    choice bootstraps consistently.

    `exchange=False` substitutes the local wrap planes for the ppermute'd
    ghosts - the identical program minus ICI traffic.  It exists ONLY for
    the phase-timing probe (solver/timing.py): the numbers it produces are
    wrong at shard boundaries whenever a mesh axis is >1.
    """
    if kernel not in ("roll", "pallas"):
        raise ValueError(f"kernel must be 'roll' or 'pallas', got {kernel!r}")
    f = stencil_ref.compute_dtype(dtype)
    n = problem.N
    inv_h2 = problem.inv_h2
    c_full = problem.a2tau2
    uneven = any(r != b for r, b in zip(topo.r_last, topo.block))
    if overlap and uneven:
        raise ValueError(
            "overlap mode requires N divisible by every mesh dim "
            f"(N={n}, mesh={topo.mesh_shape})"
        )
    multi_axes = [a for a in range(3) if topo.mesh_shape[a] > 1]

    def pallas_update(u_prev, u, ghosts, field):
        return stencil_pallas.sharded_fused_step(
            u_prev, u, ghosts, _shard_offsets(topo), n,
            inv_h2=inv_h2, mesh_shape=topo.mesh_shape, r_last=topo.r_last,
            alpha=2.0, beta=1.0,
            coeff=None if field is not None else c_full,
            c2tau2_block=field, interpret=interpret, compute_dtype=f,
        )

    def ext_update(u_prev, u, ext, bc, field):
        """Halo-extended XLA stencil, stencil_ref.leapfrog_step op order."""
        lap = stencil_ref.laplacian_ext(ext.astype(f), inv_h2)
        coeff = (
            jnp.asarray(c_full, f) if field is None else field.astype(f)
        )
        u_next = 2.0 * u.astype(f) - u_prev.astype(f) + coeff * lap
        return (u_next * bc.astype(f)).astype(dtype)

    def step_serial(u_prev, u, bc, field):
        ghosts = (
            halo.collect_ghosts(u, topo) if exchange else _self_ghosts(u)
        )
        if kernel == "pallas":
            u_in = halo.absorb_hi_ghosts(u, ghosts, topo)
            return pallas_update(u_prev, u_in, ghosts, field)
        ext = halo.place_ghosts(u, ghosts, topo)
        return ext_update(u_prev, u, ext, bc, field)

    def step_overlap(u_prev, u, bc, field):
        # The 6 ppermutes launch first and feed ONLY the face patches, so
        # the scheduler can overlap them with the bulk update below.
        ghosts = (
            halo.collect_ghosts(u, topo) if exchange else _self_ghosts(u)
        )
        if kernel == "pallas":
            bulk = pallas_update(u_prev, u, _self_ghosts(u), field)
        else:
            uc = u.astype(f)
            coeff = (
                jnp.asarray(c_full, f) if field is None else field.astype(f)
            )
            u_next = (
                2.0 * uc
                - u_prev.astype(f)
                + coeff * stencil_ref.laplacian(uc, inv_h2)
            )
            bulk = (u_next * bc.astype(f)).astype(dtype)
        if not multi_axes:
            return bulk
        # Patch the faces whose wrap neighbour crossed a shard boundary.
        # Each face's 3-plane extension is assembled directly from ghost +
        # block slices (never the full (b+2)^3 padded block - that would
        # re-add a block-sized copy per step to the loop the overlap exists
        # to shorten).
        for axis in multi_axes:
            b = topo.block[axis]
            for p in sorted({0, b - 1}):
                ext_f = _face_ext(u, ghosts, axis, p).astype(f)
                lap = stencil_ref.laplacian_ext(ext_f, inv_h2)
                fsl = [slice(None)] * 3
                fsl[axis] = slice(p, p + 1)
                fsl = tuple(fsl)
                coeff = (
                    jnp.asarray(c_full, f)
                    if field is None
                    else field[fsl].astype(f)
                )
                face = (
                    2.0 * u[fsl].astype(f)
                    - u_prev[fsl].astype(f)
                    + coeff * lap
                ) * bc[fsl].astype(f)
                starts = [p if a == axis else 0 for a in range(3)]
                bulk = lax.dynamic_update_slice(
                    bulk, face.astype(dtype), starts
                )
        return bulk

    return step_overlap if overlap else step_serial


def _make_local_comp_step(
    problem: Problem,
    topo: Topology,
    dtype,
    kernel: str,
    interpret: bool,
    exchange: bool = True,
):
    """Per-shard compensated (Kahan) step `(u, v, carry, bc, coeff) ->
    (u', v', carry')` - the sharded counterpart of
    stencil_ref.compensated_step; ghosts/masking as in `_make_local_step`.
    """
    if kernel not in ("roll", "pallas"):
        raise ValueError(f"kernel must be 'roll' or 'pallas', got {kernel!r}")
    f = stencil_ref.compute_dtype(dtype)
    if f != dtype:
        raise ValueError(
            "compensated scheme requires f32/f64 state (bf16 representation "
            "error dominates anything the compensation recovers)"
        )
    n = problem.N
    inv_h2 = problem.inv_h2

    def comp_step(u, v, carry, bc, coeff):
        ghosts = (
            halo.collect_ghosts(u, topo) if exchange else _self_ghosts(u)
        )
        if kernel == "pallas":
            u_in = halo.absorb_hi_ghosts(u, ghosts, topo)
            return stencil_pallas.sharded_compensated_step(
                u_in, v, carry, ghosts, _shard_offsets(topo), n,
                inv_h2=inv_h2, mesh_shape=topo.mesh_shape,
                r_last=topo.r_last, coeff=coeff,
                interpret=interpret, compute_dtype=f,
            )
        ext = halo.place_ghosts(u, ghosts, topo)
        lap = stencil_ref.laplacian_ext(ext.astype(f), inv_h2)
        d = (jnp.asarray(coeff, f) * lap) * bc.astype(f)
        v_next = v + d
        y = v_next - carry
        t = u + y
        carry_next = (t - u) - y
        # bc re-applied to the sum for store parity with the Pallas
        # kernel's masked store (a no-op here: u and d are both masked).
        return t * bc.astype(f), v_next, carry_next

    return comp_step


def _local_solve_fns(
    problem: Problem,
    topo: Topology,
    dtype,
    compute_errors: bool,
    kernel: str,
    overlap: bool,
    interpret: bool,
    scheme: str = "standard",
    phase: float = oracle.TWO_PI,
):
    """The per-shard solve/resume bodies (closed over by shard_map).

    `phase` shifts the analytic initial condition (ensemble lane
    identity): a shifted phase bootstraps layer 1 ANALYTICALLY (the
    exact two-level initialization - leapfrog.make_solver's reasoning),
    standard scheme only."""
    f = stencil_ref.compute_dtype(dtype)
    if scheme not in ("standard", "compensated"):
        raise ValueError(
            f"scheme must be 'standard' or 'compensated', got {scheme!r}"
        )
    compensated = scheme == "compensated"
    if compensated and overlap:
        raise ValueError("overlap mode is not available for the "
                         "compensated scheme yet")
    analytic_bootstrap = phase != oracle.TWO_PI
    if analytic_bootstrap and compensated:
        raise ValueError(
            "the sharded compensated scheme serves the reference phase "
            "only (use the single-device compensated solvers for "
            "shifted-phase lanes)"
        )
    if compensated:
        comp_step = _make_local_comp_step(
            problem, topo, dtype, kernel, interpret
        )
        step = None
    else:
        step = _make_local_step(
            problem, topo, dtype, kernel, overlap, interpret
        )

    def errors_fn(mex, mey, mez, sx, sy, sz, ct):
        def errors(u, layer):
            if not compute_errors:
                z = jnp.zeros((), f)
                return z, z
            field = oracle.analytic_field(sx, sy, sz, ct[layer])
            ae, re = oracle.layer_errors(u.astype(f), field, mex, mey, mez)
            return (
                lax.pmax(ae, AXIS_NAMES),
                lax.pmax(re, AXIS_NAMES),
            )

        return errors

    def bootstrap(sx, sy, sz, bcx, bcy, bcz, ct, field):
        """Layers 0 and 1 (calculate_start, mpi_new.cpp:271-316).

        Returns (bc, carry0) where carry0 is the scan carry at layer 1:
        (u0, u1) for the standard scheme, (u1, v1, carry1) for the
        compensated one (the same step with v = carry = 0 and coeff = C/2
        is exactly the Taylor half-step bootstrap).
        """
        bc = (
            bcx[:, None, None] * bcy[None, :, None] * bcz[None, None, :]
        )
        u0 = (oracle.analytic_field(sx, sy, sz, ct[0]) * bc).astype(dtype)
        if compensated:
            zero = jnp.zeros_like(u0)
            u1, v1, c1 = comp_step(
                u0, zero, zero, bc, 0.5 * problem.a2tau2
            )
            return bc, (u1, v1, c1), u1
        if analytic_bootstrap:
            # Shifted phases have nonzero initial velocity; layer 1 is
            # the exact analytic initialization (leapfrog.make_solver).
            u1 = (
                oracle.analytic_field(sx, sy, sz, ct[1]) * bc
            ).astype(dtype)
            return bc, (u0, u1), u1
        # Layer 1 derived from the step function (u1 = (u0 + step(u0, u0))/2
        # == u0 + C/2 lap(u0)), so the kernel choice and a variable-c field
        # bootstrap consistently - same trick as leapfrog.make_solver.
        s = step(u0, u0, bc, field)
        u1 = (0.5 * (u0.astype(f) + s.astype(f))).astype(dtype)
        return bc, (u0, u1), u1

    def scan_layers(step_args, carry0, xs, errors):
        # `xs` holds the layer indices to march - `arange(start+1, stop+1)`
        # for solve/resume, `start + 1 + arange(L)` with a RUNTIME start for
        # the supervisor's cached chunk program.  One body serves all three,
        # which is what keeps resumed/supervised layers bitwise-identical.
        bc, field = step_args

        if compensated:
            def body(carry, layer):
                u, v, c = carry
                u2, v2, c2 = comp_step(u, v, c, bc, problem.a2tau2)
                ae, re = errors(u2, layer)
                return (u2, v2, c2), (ae, re)
        else:
            def body(carry, layer):
                u_prev, u = carry
                u_next = step(u_prev, u, bc, field)
                ae, re = errors(u_next, layer)
                return (u, u_next), (ae, re)

        return lax.scan(body, carry0, xs)

    def final_state(carry):
        """(u_prev, u_cur) from the scan carry; the compensated carry
        reconstructs u_prev from the increment (leapfrog.py rationale)."""
        if compensated:
            u, v, c = carry
            return u - v, u
        return carry

    return errors_fn, bootstrap, scan_layers, final_state


def _replicated_inputs(problem, topo, dtype, phase: float = oracle.TWO_PI):
    """The small closed-over program inputs (factors, masks, time table)."""
    f = stencil_ref.compute_dtype(dtype)
    sx, sy, sz = _padded_factors(problem, topo, f)
    (bcx, bcy, bcz), (mex, mey, mez) = _masks(problem, topo, f)
    ct = oracle.time_factor_table(problem, f, phase)
    return (sx, sy, sz), (bcx, bcy, bcz), (mex, mey, mez), ct


def make_sharded_solver(
    problem: Problem,
    topo: Topology,
    mesh: jax.sharding.Mesh,
    dtype=jnp.float32,
    compute_errors: bool = True,
    kernel: str = "roll",
    overlap: bool = False,
    interpret: bool = False,
    has_field: bool = False,
    stop_step: Optional[int] = None,
    scheme: str = "standard",
    phase: float = oracle.TWO_PI,
):
    """Build the jitted end-to-end sharded solver.

    Returns the jitted runner: call `runner()` (constant speed) or, when
    `has_field`, `runner(field)` with `field` a padded (topo.padded)
    tau^2 c^2 array (sharded or host; jit shards it P("x","y","z")).
    Output is (u_prev, u_cur, abs_errs, rel_errs) with u_* sharded
    P("x","y","z") and the error vectors replicated.  `phase` shifts the
    analytic initial condition (standard scheme, constant speed only -
    the analytic layer-1 bootstrap has no closed form under variable c).
    """
    nsteps = problem.timesteps if stop_step is None else stop_step
    if not 1 <= nsteps <= problem.timesteps:
        raise ValueError(
            f"stop_step must be in [1, {problem.timesteps}], got {nsteps}"
        )
    f = stencil_ref.compute_dtype(dtype)
    if phase != oracle.TWO_PI and has_field:
        raise ValueError(
            "a shifted phase bootstraps layer 1 from the analytic "
            "solution, which only exists for constant speed; use the "
            "reference phase with c2tau2_field"
        )
    (sx, sy, sz), bcs, mes, ct = _replicated_inputs(
        problem, topo, dtype, phase
    )
    if scheme == "compensated" and has_field:
        raise ValueError(
            "compensated scheme does not support a variable-c field yet"
        )
    errors_fn, bootstrap, scan_layers, final_state = _local_solve_fns(
        problem, topo, dtype, compute_errors, kernel, overlap, interpret,
        scheme, phase,
    )

    compensated = scheme == "compensated"

    def local_solve(sx, sy, sz, bcx, bcy, bcz, mex, mey, mez, ct, *rest):
        field = rest[0] if has_field else None
        errors = errors_fn(mex, mey, mez, sx, sy, sz, ct)
        bc, carry0, u1 = bootstrap(sx, sy, sz, bcx, bcy, bcz, ct, field)
        a0 = r0 = jnp.zeros((), f)  # layer 0 assigned from the oracle
        a1, r1 = errors(u1, 1)
        carry, (abs_t, rel_t) = scan_layers(
            (bc, field), carry0, jnp.arange(2, nsteps + 1), errors
        )
        u_prev, u_cur = final_state(carry)
        abs_all = jnp.concatenate([jnp.stack([a0, a1]), abs_t])
        rel_all = jnp.concatenate([jnp.stack([r0, r1]), rel_t])
        if compensated:
            # v and the Kahan carry ride out for checkpointing.
            _, v, kc = carry
            return u_prev, u_cur, abs_all, rel_all, v, kc
        return u_prev, u_cur, abs_all, rel_all

    in_specs = [
        P("x"), P("y"), P("z"),
        P("x"), P("y"), P("z"),
        P("x"), P("y"), P("z"),
        P(),
    ]
    if has_field:
        in_specs.append(P(*AXIS_NAMES))
    out_specs = [P(*AXIS_NAMES), P(*AXIS_NAMES), P(), P()]
    if compensated:
        out_specs += [P(*AXIS_NAMES), P(*AXIS_NAMES)]
    # check_vma=False: the Pallas interpret path (CPU tests/dryruns) does
    # not yet propagate varying-mesh-axes through in-kernel concatenates;
    # parity with the roll kernel is pinned by tests instead.
    sharded_fn = jax.shard_map(
        local_solve,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )

    def run(*rt_args):
        return sharded_fn(sx, sy, sz, *bcs, *mes, ct, *rt_args)

    return jax.jit(run)


def make_sharded_resumer(
    problem: Problem,
    topo: Topology,
    mesh: jax.sharding.Mesh,
    start_step: int,
    dtype=jnp.float32,
    compute_errors: bool = True,
    kernel: str = "roll",
    overlap: bool = False,
    interpret: bool = False,
    has_field: bool = False,
    scheme: str = "standard",
):
    """Jitted re-entry into the sharded time loop at layer `start_step`.

    `runner(u_prev, u_cur[, field])` marches to problem.timesteps; the
    per-step op sequence is identical to `make_sharded_solver`'s, so a
    resumed run reproduces the uninterrupted one (tests/test_sharded_ckpt).
    Error entries before start_step+1 are zero, as in `leapfrog.resume`.
    """
    nsteps = problem.timesteps
    if not 1 <= start_step <= nsteps:
        raise ValueError(
            f"start_step must be in [1, {nsteps}], got {start_step}"
        )
    f = stencil_ref.compute_dtype(dtype)
    (sx, sy, sz), bcs, mes, ct = _replicated_inputs(problem, topo, dtype)
    errors_fn, _, scan_layers, final_state = _local_solve_fns(
        problem, topo, dtype, compute_errors, kernel, overlap, interpret,
        scheme,
    )
    compensated = scheme == "compensated"
    n_state = 3 if compensated else 2

    def local_resume(*args):
        state = args[:n_state]
        (sx, sy, sz, bcx, bcy, bcz, mex, mey, mez, ct, *rest) = (
            args[n_state:]
        )
        field = rest[0] if has_field else None
        errors = errors_fn(mex, mey, mez, sx, sy, sz, ct)
        bc = bcx[:, None, None] * bcy[None, :, None] * bcz[None, None, :]
        carry, (abs_t, rel_t) = scan_layers(
            (bc, field), state, jnp.arange(start_step + 1, nsteps + 1),
            errors,
        )
        u_p, u_c = final_state(carry)
        head = jnp.zeros((start_step + 1,), f)
        abs_all = jnp.concatenate([head, abs_t])
        rel_all = jnp.concatenate([head, rel_t])
        if compensated:
            _, v, kc = carry
            return u_p, u_c, abs_all, rel_all, v, kc
        return u_p, u_c, abs_all, rel_all

    state_spec = P(*AXIS_NAMES)
    in_specs = [state_spec] * n_state + [
        P("x"), P("y"), P("z"),
        P("x"), P("y"), P("z"),
        P("x"), P("y"), P("z"),
        P(),
    ]
    if has_field:
        in_specs.append(P(*AXIS_NAMES))
    out_specs = [state_spec, state_spec, P(), P()]
    if compensated:
        out_specs += [state_spec, state_spec]
    sharded_fn = jax.shard_map(
        local_resume,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )

    def run(*state_and_args):
        state = tuple(
            jnp.asarray(a, dtype) for a in state_and_args[:n_state]
        )
        rt_args = state_and_args[n_state:]
        return sharded_fn(*state, sx, sy, sz, *bcs, *mes, ct, *rt_args)

    return jax.jit(run)


def make_sharded_chunk_runner(
    problem: Problem,
    topo: Topology,
    mesh: jax.sharding.Mesh,
    length: int,
    dtype=jnp.float32,
    compute_errors: bool = True,
    kernel: str = "roll",
    overlap: bool = False,
    interpret: bool = False,
    has_field: bool = False,
    scheme: str = "standard",
):
    """Fixed-length sharded re-entry for supervised solves.

    `runner(u_prev, u_cur, start[, field])` (compensated: `runner(u, v,
    carry, start[, field])`) marches layers start+1..start+length with a
    RUNTIME `start` - one compiled program per chunk length, reused for
    every chunk (run/supervisor.py).  The scan body is the same
    `scan_layers` closure `make_sharded_solver`/`make_sharded_resumer`
    run, so supervised layers stay bitwise-identical to an uninterrupted
    sharded solve's.
    """
    if length < 1:
        raise ValueError(f"chunk length must be >= 1, got {length}")
    f = stencil_ref.compute_dtype(dtype)
    (sx, sy, sz), bcs, mes, ct = _replicated_inputs(problem, topo, dtype)
    errors_fn, _, scan_layers, final_state = _local_solve_fns(
        problem, topo, dtype, compute_errors, kernel, overlap, interpret,
        scheme,
    )
    compensated = scheme == "compensated"
    n_state = 3 if compensated else 2

    def local_chunk(*args):
        state = args[:n_state]
        (start, sx, sy, sz, bcx, bcy, bcz, mex, mey, mez, ct, *rest) = (
            args[n_state:]
        )
        field = rest[0] if has_field else None
        errors = errors_fn(mex, mey, mez, sx, sy, sz, ct)
        bc = bcx[:, None, None] * bcy[None, :, None] * bcz[None, None, :]
        xs = start + 1 + jnp.arange(length, dtype=jnp.int32)
        carry, (abs_t, rel_t) = scan_layers((bc, field), state, xs, errors)
        u_p, u_c = final_state(carry)
        if compensated:
            _, v, kc = carry
            return u_p, u_c, abs_t, rel_t, v, kc
        return u_p, u_c, abs_t, rel_t

    state_spec = P(*AXIS_NAMES)
    in_specs = [state_spec] * n_state + [
        P(),
        P("x"), P("y"), P("z"),
        P("x"), P("y"), P("z"),
        P("x"), P("y"), P("z"),
        P(),
    ]
    if has_field:
        in_specs.append(P(*AXIS_NAMES))
    out_specs = [state_spec, state_spec, P(), P()]
    if compensated:
        out_specs += [state_spec, state_spec]
    sharded_fn = jax.shard_map(
        local_chunk,
        mesh=mesh,
        in_specs=tuple(in_specs),
        out_specs=tuple(out_specs),
        check_vma=False,
    )

    def run(*state_start_args):
        state = tuple(
            jnp.asarray(a, dtype) for a in state_start_args[:n_state]
        )
        start = state_start_args[n_state]
        rt_args = state_start_args[n_state + 1:]
        return sharded_fn(
            *state, start, sx, sy, sz, *bcs, *mes, ct, *rt_args
        )

    return jax.jit(run)


def _default_interpret() -> bool:
    """Pallas needs Mosaic (TPU); anywhere else run the kernel interpreted
    so CPU tests/dryruns exercise the identical program structure."""
    return jax.default_backend() != "tpu"


def _run_timed(runner, rt_args):
    """(outputs, abs_np, rel_np, init_s, solve_s); outputs is the runner's
    tuple (u_prev, u_cur, abs, rel[, v, carry])."""
    t0 = time.perf_counter()
    compiled = runner.lower(*rt_args).compile()
    t1 = time.perf_counter()
    out = compiled(*rt_args)
    jax.block_until_ready(out)
    # The small error-vector readback inside the timed region proves the
    # program actually ran (see leapfrog._timed_compile_run).
    abs_np = np.asarray(out[2], dtype=np.float64)
    rel_np = np.asarray(out[3], dtype=np.float64)
    t2 = time.perf_counter()
    return out, abs_np, rel_np, t1 - t0, t2 - t1


def _resolve_mesh(problem, mesh_shape, devices):
    if devices is None:
        devices = jax.devices()
    if mesh_shape is None:
        mesh_shape = choose_mesh_shape(len(devices))
    topo = Topology(N=problem.N, mesh_shape=mesh_shape)
    if len(devices) < topo.n_devices:
        raise ValueError(
            f"mesh {mesh_shape} needs {topo.n_devices} devices, "
            f"only {len(devices)} available"
        )
    mesh = build_mesh(mesh_shape, devices[: topo.n_devices])
    return topo, mesh


def solve_sharded(
    problem: Problem,
    mesh_shape: Optional[Tuple[int, int, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    dtype=jnp.float32,
    compute_errors: bool = True,
    kernel: str = "roll",
    overlap: bool = False,
    interpret: Optional[bool] = None,
    c2tau2_field: Optional[np.ndarray] = None,
    stop_step: Optional[int] = None,
    scheme: str = "standard",
    phase: float = oracle.TWO_PI,
) -> SolveResult:
    """Compile + run the distributed solve; returns the same SolveResult as
    the single-device path (errors are cross-device maxima).

    `mesh_shape` defaults to a near-cubic factorization of the available
    device count (MPI_Dims_create analog, mpi_sol.cpp:407).  `kernel`
    selects the per-shard hot kernel ("pallas" = the fused slab kernel,
    "roll" = the XLA reference stencil); `overlap` requests
    compute/communication overlap (even shard splits only).
    `c2tau2_field` is an (N, N, N) host array from
    `stencil_ref.make_c2tau2_field`; pair it with compute_errors=False
    (the analytic oracle holds for constant speed only).  `phase` shifts
    the analytic initial condition (standard scheme, constant speed
    only) - the lane identity of the sharded ensemble engine.
    """
    topo, mesh = _resolve_mesh(problem, mesh_shape, devices)
    if interpret is None:
        interpret = _default_interpret()
    has_field = c2tau2_field is not None
    runner = make_sharded_solver(
        problem, topo, mesh, dtype, compute_errors, kernel, overlap,
        interpret, has_field, stop_step, scheme, phase,
    )
    rt_args = ()
    if has_field:
        f = stencil_ref.compute_dtype(dtype)
        rt_args = (jnp.asarray(pad_field(c2tau2_field, topo), dtype=f),)
    out, abs_np, rel_np, init_s, solve_s = _run_timed(runner, rt_args)
    result = SolveResult(
        problem=problem,
        u_prev=out[0],
        u_cur=out[1],
        abs_errors=abs_np,
        rel_errors=rel_np,
        init_seconds=init_s,
        solve_seconds=solve_s,
        steps_computed=stop_step,
        final_step=stop_step if stop_step is not None else problem.timesteps,
        comp_v=out[4] if scheme == "compensated" else None,
        comp_carry=out[5] if scheme == "compensated" else None,
    )
    obs_metrics.record_solve(
        result, "sharded", scheme=scheme,
        with_field=c2tau2_field is not None,
    )
    return result


def resume_sharded(
    problem: Problem,
    u_prev,
    u_cur,
    start_step: int,
    mesh_shape: Optional[Tuple[int, int, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
    dtype=jnp.float32,
    compute_errors: bool = True,
    kernel: str = "roll",
    overlap: bool = False,
    interpret: Optional[bool] = None,
    c2tau2_field: Optional[np.ndarray] = None,
    scheme: str = "standard",
    comp_v=None,
    comp_carry=None,
) -> SolveResult:
    """Re-enter the sharded time loop at layer `start_step` and run to the
    end.  `u_prev`/`u_cur` are padded (topo.padded) arrays - what
    `solve_sharded(stop_step=...)` returned and io/checkpoint.py stored.
    A compensated resume additionally takes (comp_v, comp_carry) and
    re-enters from (u_cur, v, carry); u_prev is then ignored."""
    topo, mesh = _resolve_mesh(problem, mesh_shape, devices)
    if interpret is None:
        interpret = _default_interpret()
    has_field = c2tau2_field is not None
    compensated = scheme == "compensated"
    if compensated and (comp_v is None or comp_carry is None):
        raise ValueError(
            "compensated resume needs comp_v and comp_carry"
        )
    runner = make_sharded_resumer(
        problem, topo, mesh, start_step, dtype, compute_errors, kernel,
        overlap, interpret, has_field, scheme,
    )
    if compensated:
        rt_args = (u_cur, comp_v, comp_carry)
    else:
        rt_args = (u_prev, u_cur)
    if has_field:
        f = stencil_ref.compute_dtype(dtype)
        rt_args = rt_args + (
            jnp.asarray(pad_field(c2tau2_field, topo), dtype=f),
        )
    out, abs_np, rel_np, init_s, solve_s = _run_timed(runner, rt_args)
    return SolveResult(
        problem=problem,
        u_prev=out[0],
        u_cur=out[1],
        abs_errors=abs_np,
        rel_errors=rel_np,
        init_seconds=init_s,
        solve_seconds=solve_s,
        steps_computed=problem.timesteps - start_step,
        final_step=problem.timesteps,
        comp_v=out[4] if compensated else None,
        comp_carry=out[5] if compensated else None,
    )


def gather_fundamental(u: jax.Array, problem: Problem) -> np.ndarray:
    """Fetch the (possibly padded) sharded field to host and strip padding,
    returning the (N, N, N) fundamental domain."""
    n = problem.N
    return np.asarray(u)[:n, :n, :n]
