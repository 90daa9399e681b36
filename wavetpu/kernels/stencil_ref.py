"""Pure-jnp leapfrog + 7-point Laplacian stencil (the semantic reference).

This is the XLA-fused counterpart of the reference's hot loops
(openmp_sol.cpp:157-163 interior leapfrog, openmp_sol.cpp:56-63 `Grid::laplace`,
cuda_sol_kernels.cu:24-47 `calculate_layer`).  Everything is expressed as
cyclic rolls, which is exact because of the state representation documented in
`wavetpu.core.problem`:

 * x is the fundamental periodic domain, so rolls ARE the boundary condition
   (the reference's seam `prepare_layer` update, openmp_sol.cpp:114-120, is
   the same formula with a wrapped neighbour).
 * y/z hold the Dirichlet invariant u[:,0,:] = u[:,:,0] = 0, so a cyclic roll
   delivers the correct zero neighbour for the j = N-1 / k = N-1 planes, and
   the j=0 / k=0 planes themselves are re-zeroed after each update (the
   counterpart of the reference zeroing all four y/z faces each step,
   openmp_sol.cpp:104-112).

This module is the semantic reference for any fused kernel implementation:
a Pallas kernel substituted via `make_solver(step_fn=...)` must agree with
it to rounding error on identical inputs.
"""

from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
import numpy as np

from wavetpu.core.problem import Problem


def compute_dtype(dtype):
    """bf16 state computes in f32 (the BASELINE.md stretch contract:
    bf16 storage + fp32 accumulation); everything else computes as stored."""
    return jnp.float32 if dtype == jnp.bfloat16 else dtype


def laplacian(u, inv_h2):
    """7-point Laplacian with cyclic shifts on all three axes."""
    ix, iy, iz = inv_h2
    lap = (jnp.roll(u, 1, 0) + jnp.roll(u, -1, 0) - 2.0 * u) * ix
    lap = lap + (jnp.roll(u, 1, 1) + jnp.roll(u, -1, 1) - 2.0 * u) * iy
    lap = lap + (jnp.roll(u, 1, 2) + jnp.roll(u, -1, 2) - 2.0 * u) * iz
    return lap


def apply_dirichlet(u):
    """Re-impose the Dirichlet invariant: zero the stored y=0 and z=0 planes.

    (The y=N / z=N planes are not stored; see problem.py.)
    """
    u = u.at[:, 0, :].set(0.0)
    u = u.at[:, :, 0].set(0.0)
    return u


def leapfrog_step(u_prev, u, problem: Problem):
    """u_next = 2u - u_prev + a^2 tau^2 lap(u), Dirichlet re-imposed.

    The uniform interior update of the reference (openmp_sol.cpp:160) which,
    on the fundamental domain, also covers the periodic seam.  bf16 state
    computes in f32 and stores back in bf16.
    """
    f = compute_dtype(u.dtype)
    uc = u.astype(f)
    c = jnp.asarray(problem.a2tau2, dtype=f)
    u_next = 2.0 * uc - u_prev.astype(f) + c * laplacian(uc, problem.inv_h2)
    return apply_dirichlet(u_next).astype(u.dtype)


def taylor_half_step(u0, problem: Problem):
    """Layer-1 bootstrap: u1 = u0 + (a^2 tau^2 / 2) lap(u0)  (uses u_t(0)=0).

    Reference: openmp_sol.cpp:137-144 and the seam's n==1 coefficients at
    openmp_sol.cpp:117 (factor 1 on u0, none on u^{-1}, half on the Laplacian),
    which are exactly this formula.
    """
    f = compute_dtype(u0.dtype)
    uc = u0.astype(f)
    c = jnp.asarray(0.5 * problem.a2tau2, dtype=f)
    u1 = uc + c * laplacian(uc, problem.inv_h2)
    return apply_dirichlet(u1).astype(u0.dtype)


def make_c2tau2_field(
    problem: Problem, c2_fn: Callable[[np.ndarray, np.ndarray, np.ndarray], np.ndarray]
) -> np.ndarray:
    """Evaluate tau^2 * c^2(x, y, z) on the fundamental grid, host-side f64.

    `c2_fn` takes broadcastable (x, y, z) coordinate arrays and returns the
    squared wave speed.  The constant-speed problem is `c2_fn = lambda
    x, y, z: problem.a2`; the result then equals `problem.a2tau2` everywhere
    (pinned by tests/test_variable_c.py).

    Variable wave speed is a capability extension over the reference (its
    a^2 is hardcoded, openmp_sol.cpp:207); the analytic oracle only holds
    for constant speed, so variable-c runs should pass compute_errors=False.
    """
    n = problem.N
    x = (np.arange(n, dtype=np.float64) * problem.hx)[:, None, None]
    y = (np.arange(n, dtype=np.float64) * problem.hy)[None, :, None]
    z = (np.arange(n, dtype=np.float64) * problem.hz)[None, None, :]
    c2 = np.broadcast_to(
        np.asarray(c2_fn(x, y, z), dtype=np.float64), (n, n, n)
    )
    return c2 * problem.tau**2


C2_PRESET_NAMES = ("constant", "gaussian-lens", "two-layer")


def make_preset_c2tau2_field(problem: Problem, name: str) -> np.ndarray:
    """The named tau^2 c^2(x,y,z) presets - ONE source of truth shared by
    the CLI (`--c2-field`) and the serving API (`c2_field`), so the same
    preset name always means the same physics on both surfaces.

    constant: c^2 = a^2 everywhere (collapses to a2tau2; pinned by
    tests/test_variable_c.py).  gaussian-lens: a slow-speed lens dipping
    to a^2/2 at the domain centre.  two-layer: a discontinuous interface
    with the far z half running at DOUBLE c^2 (note: Courant-unstable at
    configs whose constant-c C is already near the bound - the serving
    watchdog tests rely on exactly that).
    """
    a2 = problem.a2

    def _gaussian_lens(x, y, z):
        s2 = 2.0 * (problem.Lx / 8.0) ** 2
        r2 = (
            (x - problem.Lx / 2) ** 2
            + (y - problem.Ly / 2) ** 2
            + (z - problem.Lz / 2) ** 2
        )
        return a2 * (1.0 - 0.5 * np.exp(-r2 / s2))

    presets = {
        "constant": lambda x, y, z: a2 * np.ones_like(x + y + z),
        "gaussian-lens": _gaussian_lens,
        "two-layer": lambda x, y, z: np.where(
            z < problem.Lz / 2, a2, 2.0 * a2
        ) + 0.0 * x + 0.0 * y,
    }
    if name not in presets:
        raise ValueError(
            f"c2 preset must be one of {sorted(presets)}, got {name!r}"
        )
    return make_c2tau2_field(problem, presets[name])


def make_variable_c_step(c2tau2_field):
    """A solver step with spatially varying speed:
    u_next = 2u - u_prev + tau^2 c^2(x,y,z) lap(u).

    Returns a `ParamStep`: the field rides through the jitted program as a
    runtime argument (closing over it would embed an N^3 HLO literal -
    512 MB at N=512; see solver.leapfrog.ParamStep).  Slots into
    `make_solver(step_fn=...)` like any other kernel, or call it directly
    as `(u_prev, u, problem)`.
    """
    from wavetpu.solver.leapfrog import ParamStep

    def step(u_prev, u, problem: Problem, field):
        f = compute_dtype(u.dtype)
        uc = u.astype(f)
        coeff = jnp.asarray(field, dtype=f)
        u_next = (
            2.0 * uc - u_prev.astype(f) + coeff * laplacian(uc, problem.inv_h2)
        )
        return apply_dirichlet(u_next).astype(u.dtype)

    return ParamStep(step, ParamStep.materialize(np.asarray(c2tau2_field)))


def compensated_step(u, v, carry, problem: Problem, coeff=None):
    """One step of the compensated (Kahan) incremental leapfrog.

    Algebraically identical to `leapfrog_step` via the increment form
    v_n = u_n - u_{n-1}:

        v_{n+1} = v_n + C*lap(u_n)
        u_{n+1} = u_n + v_{n+1}          (compensated two-sum)

    but numerically far better in f32: the standard form adds the tiny
    update C*lap(u) (~1e-5 at N=512) into O(1) state and loses its low
    bits every step - measured 1.09e-3 L-inf error at N=512/1000 vs the
    ~4e-6 discretization bound (v5e).  Here the increment
    accumulates in its own small-magnitude buffer and the u addition runs
    Kahan-compensated through `carry`, so rounding stays at the one-time
    f32 representation level (measured ~2e-7 vs f64 at N=128/1000 - a
    ~7000x reduction; the analytic error then equals f64's).

    The Dirichlet mask is applied to the increment only: u, v, carry all
    start masked and sums of masked fields stay masked.

    `coeff` defaults to a2tau2; the layer-1 bootstrap is this same step
    with v = carry = 0 and coeff = a2tau2/2 (then u1 = u0 + (C/2)lap(u0),
    the Taylor half-step, openmp_sol.cpp:137-144).
    """
    c = jnp.asarray(
        problem.a2tau2 if coeff is None else coeff, dtype=u.dtype
    )
    d = apply_dirichlet(c * laplacian(u, problem.inv_h2))
    v_next = v + d
    # Kahan two-sum: u_next = u + v_next with error fed back via carry.
    y = v_next - carry
    t = u + y
    carry_next = (t - u) - y
    return t, v_next, carry_next


def laplacian_ext(ext, inv_h2):
    """7-point Laplacian of the interior of a halo-extended block.

    `ext` has one ghost cell on each side of each axis: shape (bx+2, by+2,
    bz+2); the result has shape (bx, by, bz).  Used by the sharded solver
    where ghost planes arrive via `ppermute` instead of rolls.
    """
    ix, iy, iz = inv_h2
    c = ext[1:-1, 1:-1, 1:-1]
    lap = (ext[:-2, 1:-1, 1:-1] + ext[2:, 1:-1, 1:-1] - 2.0 * c) * ix
    lap = lap + (ext[1:-1, :-2, 1:-1] + ext[1:-1, 2:, 1:-1] - 2.0 * c) * iy
    lap = lap + (ext[1:-1, 1:-1, :-2] + ext[1:-1, 1:-1, 2:] - 2.0 * c) * iz
    return lap
