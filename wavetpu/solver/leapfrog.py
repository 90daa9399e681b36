"""Single-device time-stepping driver.

Replaces the reference's `calculate_start` + `calculate_num_sol` loops
(openmp_sol.cpp:123-167, mpi_new.cpp:271-372) with one jitted program:
layer-0/1 bootstrap followed by a `lax.scan` over the remaining steps.

Design notes (TPU-first, not a translation):

 * The reference rotates three buffers `grids[n % 3]` (mpi_new.cpp:131,338).
   In functional JAX the scan carry is simply (u_prev, u_cur) - two live
   buffers, with XLA double-buffering the output of each step.
 * The reference's fused error path re-evaluates the analytic solution with
   three sines per point per step (mpi_new.cpp:340).  Here the separable
   oracle (verify/oracle.py) reduces that to broadcasted 1-D factors.
 * Per-layer L-inf errors are accumulated as scan outputs, the analog of
   `max_abs_errors.push_back` (mpi_new.cpp:350) - no host round-trips inside
   the loop.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from wavetpu import jaxcache
from wavetpu.core.problem import Problem
from wavetpu.kernels import stencil_ref
from wavetpu.obs import metrics as obs_metrics
from wavetpu.obs import tracing
from wavetpu.verify import oracle


@dataclasses.dataclass
class SolveResult:
    problem: Problem
    u_prev: jax.Array          # layer timesteps-1 (fundamental (N,N,N) domain)
    u_cur: jax.Array           # layer timesteps
    abs_errors: np.ndarray     # per-layer L-inf abs error, shape (timesteps+1,)
    rel_errors: np.ndarray     # per-layer L-inf rel error, shape (timesteps+1,)
    init_seconds: float = 0.0
    solve_seconds: float = 0.0
    steps_computed: Optional[int] = None  # steps THIS run marched (throughput)
    final_step: Optional[int] = None      # layer index u_cur holds (checkpoint)
    # Compensated-scheme auxiliary state (None on the standard scheme):
    # the increment buffer v = u_n - u_{n-1} and the Kahan carry at
    # final_step - what a checkpoint must store for a bitwise resume.
    comp_v: Optional[jax.Array] = None
    comp_carry: Optional[jax.Array] = None

    @property
    def gcells_per_second(self) -> float:
        steps = (
            self.steps_computed
            if self.steps_computed is not None
            else self.problem.timesteps
        )
        total = self.problem.cells_per_step * steps
        return total / self.solve_seconds / 1e9 if self.solve_seconds else 0.0


class ParamStep(NamedTuple):
    """A step function with runtime array parameters.

    `fn(u_prev, u, problem, params) -> u_next`; `params` (a pytree of
    arrays, e.g. the variable-c field) is threaded through the jitted
    program as a runtime ARGUMENT, not closed over.  Closing over a large
    field would embed it as an HLO literal - at N=512 that is a 512 MB
    constant in the program, recompiled for every field.
    """

    fn: Callable
    params: object

    def __call__(self, u_prev, u, problem):
        """Direct use outside a solver (tests, one-off steps)."""
        return self.fn(u_prev, u, problem, self.params)

    @staticmethod
    def materialize(array):
        """Convert a field to a device array and force the host->device
        transfer NOW, so the upload never lands inside the first solve's
        timed region."""
        dev = jnp.asarray(array)
        np.asarray(dev[:1, :1, :1] if dev.ndim == 3 else dev.ravel()[:1])
        return dev


def _as_param_step(step_fn):
    """Normalize the three accepted step_fn forms to (fn4, params)."""
    if step_fn is None:
        return (
            lambda up, u, p, _: stencil_ref.leapfrog_step(up, u, p)
        ), ()
    if isinstance(step_fn, ParamStep):
        return step_fn.fn, step_fn.params
    return (lambda up, u, p, _, f=step_fn: f(up, u, p)), ()


def _error_fn(problem: Problem, dtype, phase: float = oracle.TWO_PI):
    """Returns (u, n) -> (abs_e, rel_e) with precomputed factors closed over.

    The oracle always evaluates in the compute dtype (f32 for bf16 state):
    the error should measure the solver, not the bf16 quantization of the
    analytic field.  `phase` is the initial time phase of the analytic
    solution (default: the reference's 2*pi; per-lane in the ensemble).
    """
    f_dtype = stencil_ref.compute_dtype(dtype)
    sx, sy, sz = oracle.spatial_factors(problem, f_dtype)
    ct_table = oracle.time_factor_table(problem, f_dtype, phase)
    mask = jnp.asarray(oracle.interior_masks_1d(problem.N))

    def errors(u, n):
        f = oracle.analytic_field(sx, sy, sz, ct_table[n])
        return oracle.layer_errors(u.astype(f_dtype), f, mask, mask, mask)

    return errors


def analytic_layer(
    problem: Problem, dtype=jnp.float32, phase: float = oracle.TWO_PI,
    n: int = 0,
) -> jax.Array:
    """The analytic solution at layer n, Dirichlet re-imposed.

    n=0 is the reference's layer-0 fill (`calculate_start`,
    openmp_sol.cpp:126-133); n=1 is the EXACT two-level initialization a
    phase-shifted lane bootstraps with (see make_solver).  bf16 state
    evaluates in f32 and rounds once.
    """
    f = stencil_ref.compute_dtype(dtype)
    sx, sy, sz = oracle.spatial_factors(problem, f)
    ct = oracle.time_factor(problem, n, f, phase)
    u = oracle.analytic_field(sx, sy, sz, ct)
    return stencil_ref.apply_dirichlet(u).astype(dtype)


def initial_layer0(
    problem: Problem, dtype=jnp.float32, phase: float = oracle.TWO_PI
) -> jax.Array:
    """Layer 0: the analytic solution at t=0 (see `analytic_layer`)."""
    return analytic_layer(problem, dtype, phase, 0)


def analytic_increment_layer1(
    problem: Problem, dtype=jnp.float32, phase: float = oracle.TWO_PI
) -> jax.Array:
    """The exact analytic layer-0->1 increment Sx Sy Sz (ct(1) - ct(0)),
    Dirichlet re-imposed - the v1 a shifted-phase COMPENSATED solve
    bootstraps with (the increment of the exact two-level
    initialization).

    Deliberately a pure product, NOT u1 - u0: XLA-CPU FMA-contracts the
    field subtract with the analytic product feeding it differently
    between solo and vmapped program shapes (measured ~1 ulp on this
    jaxlib), which would break the ensemble's bitwise lane-parity
    contract; a product-only expression compiles identically everywhere
    (the same reasoning that picked the analytic bootstrap over a
    tau*u_t correction term - see make_solver)."""
    f = stencil_ref.compute_dtype(dtype)
    sx, sy, sz = oracle.spatial_factors(problem, f)
    dct = (
        oracle.time_factor(problem, 1, f, phase)
        - oracle.time_factor(problem, 0, f, phase)
    )
    u = oracle.analytic_field(sx, sy, sz, dct)
    return stencil_ref.apply_dirichlet(u).astype(dtype)


def initial_state(problem: Problem, dtype=jnp.float32) -> Tuple[jax.Array, jax.Array]:
    """Layers 0 and 1: analytic init + (constant-speed) Taylor half-step.

    Reference: `calculate_start` (openmp_sol.cpp:123-145).  Layer 0 fills the
    whole grid from the analytic solution; layer 1 is the half-step
    u1 = u0 + (a^2 tau^2 / 2) lap(u0), with boundary planes re-imposed.
    bf16 state bootstraps in f32 and rounds once at the end.

    Note: `make_solver` derives layer 1 from its step function instead (so
    variable-c kernels bootstrap with their own field); this helper is the
    standalone constant-speed form for tests and the driver entry hook.
    """
    u0 = initial_layer0(problem, dtype)
    u1 = stencil_ref.taylor_half_step(u0, problem)
    return u0, u1.astype(dtype)


def _scan_layers_xs(
    problem: Problem,
    step: Callable,
    step_params,
    errors: Callable,
    compute_errors: bool,
    dtype,
    u_prev,
    u_cur,
    xs,
):
    """March one layer per element of `xs` (the layer indices, which may be
    traced - the supervisor's chunk runners pass `start + 1 + arange(L)`
    with a runtime `start` so one compiled program serves every chunk).

    The single scan body shared by `make_solver`, `resume`, and
    `make_chunk_runner` - keeping it shared is what makes a resumed or
    supervised run's op sequence identical to the uninterrupted run's (the
    bitwise-equality invariant of tests/test_checkpoint.py and
    tests/test_supervisor.py).
    """

    err_dtype = stencil_ref.compute_dtype(dtype)

    def body(carry, n):
        u_prev, u = carry
        u_next = step(u_prev, u, problem, step_params)
        if compute_errors:
            ae, re = errors(u_next, n)
        else:
            ae = re = jnp.zeros((), err_dtype)
        return (u, u_next), (ae, re)

    return jax.lax.scan(body, (u_prev, u_cur), xs)


def _scan_layers(
    problem: Problem,
    step: Callable,
    step_params,
    errors: Callable,
    compute_errors: bool,
    dtype,
    u_prev,
    u_cur,
    start: int,
    stop: int,
):
    """March layers start+1..stop from carry (layer start-1, layer start)."""
    return _scan_layers_xs(
        problem, step, step_params, errors, compute_errors, dtype,
        u_prev, u_cur, jnp.arange(start + 1, stop + 1),
    )


def _timed_compile_run(runner, example_args=(), sync=None, *,
                       path: str, **attrs):
    """lower/compile then execute; returns (outputs, init_s, solve_s) with
    the reference's two timing phases (mpi_new.cpp:472-474, 354-357).

    `sync(out)` must force a (small) device-to-host transfer: a readback
    proves the program ran, so the transfer sits INSIDE the timed region
    next to `block_until_ready`.  Keep it small (e.g. the per-layer error
    vector, not a field).

    The two phases are the program spans `solve.prepare` (trace, lower,
    and XLA compile or persistent-cache load; `attrs` ride on it, plus
    `compiled`: whether no persistent-cache hit served the compile) and
    `solve.run` (dispatch, `block_until_ready`, the readback), both
    labelled with the entry's `path` (the `record_solve` label).  The
    entry's own `solve.finish` span follows them; only the entry's
    Python build of `runner` before this call (jax.jit is lazy: closures
    and a few small constants, milliseconds once warm) lies outside the
    three.
    """
    xla_hits = jaxcache.shared_xla_hit_counter()
    with tracing.span("solve.prepare", path=path, **attrs) as sp:
        hits0 = xla_hits.hits
        t0 = time.perf_counter()
        lowered = runner.lower(*example_args).compile()
        t1 = time.perf_counter()
        sp["compiled"] = xla_hits.hits == hits0
    with tracing.span("solve.run", path=path):
        out = lowered(*example_args)
        jax.block_until_ready(out)
        if sync is not None:
            sync(out)
        t2 = time.perf_counter()
    return out, t1 - t0, t2 - t1


def make_solver(
    problem: Problem,
    dtype=jnp.float32,
    step_fn: Optional[Callable] = None,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    phase: float = oracle.TWO_PI,
) -> Tuple[Callable, object]:
    """Build the jitted end-to-end solver.

    Returns `(runner, step_params)`; call `runner(step_params)`.  For the
    default and plain-step paths `step_params` is just `()`; a `ParamStep`
    kernel's array parameters (e.g. the variable-c field) ride through as
    runtime arguments (see ParamStep for why they must not be closed over).

    `step_fn(u_prev, u, problem) -> u_next` defaults to the jnp-roll stencil;
    the Pallas kernel slots in via the same signature, and `ParamStep` adds
    a params argument.

    Layer 1 is derived FROM the step function - u1 = (u0 + step(u0, u0))/2
    equals the Taylor half-step u0 + (coeff/2)*lap(u0) for any leapfrog-form
    kernel - so a variable-c kernel bootstraps with its own c^2(x,y,z), not
    the constant a^2 (reference: openmp_sol.cpp:137-144).

    `stop_step` halts the march after that layer (default: run to
    `problem.timesteps`).  tau stays `T / timesteps` regardless, so a stopped
    run is the exact prefix of the full one - the state a checkpoint captures
    (io/checkpoint.py) and `resume` continues from.

    `phase` sets the analytic initial condition's time phase (lane identity
    in the ensemble engine); the default 2*pi reproduces the reference.
    A shifted phase has NONZERO initial velocity u_t(0) = -a_t sin(phase)
    * Sx Sy Sz, which the reference's velocity-less Taylor bootstrap
    u1 = u0 + (C/2) lap(u0) cannot represent - using it anyway would
    integrate a DIFFERENT initial-value problem than the oracle measures
    and report O(1) "error".  Shifted-phase solves therefore bootstrap
    layer 1 ANALYTICALLY (u1 = Sx Sy Sz cos(a_t tau + phase), the exact
    two-level initialization), which the oracle is exact for; the
    reference phase keeps the step-derived bootstrap, so the default
    program is bit-identical to the phase-less solver.  (An explicit
    tau * u_t(0) correction term was tried first: LLVM FMA-contracts
    the add differently between the solo and vmapped program shapes on
    XLA-CPU - even across optimization_barrier - breaking bitwise lane
    parity; the analytic bootstrap sidesteps fusion entirely.)
    """
    step, step_params = _as_param_step(step_fn)
    errors = _error_fn(problem, dtype, phase)
    analytic_bootstrap = phase != oracle.TWO_PI
    if analytic_bootstrap and jax.tree_util.tree_leaves(step_params):
        # Runtime step params mark a variable-c kernel (ParamStep); the
        # analytic bootstrap would silently initialize from the
        # constant-speed solution and solve a different IVP.
        raise ValueError(
            "a shifted phase bootstraps layer 1 from the analytic "
            "solution, which only exists for constant speed; use the "
            "reference phase with variable-c step functions"
        )
    nsteps = problem.timesteps if stop_step is None else stop_step
    if not 1 <= nsteps <= problem.timesteps:
        raise ValueError(
            f"stop_step must be in [1, {problem.timesteps}], got {nsteps}"
        )

    def run(step_params):
        u0 = initial_layer0(problem, dtype, phase)
        f = stencil_ref.compute_dtype(dtype)
        if analytic_bootstrap:
            u1 = analytic_layer(problem, dtype, phase, 1)
        else:
            u1 = (
                0.5 * (
                    u0.astype(f)
                    + step(u0, u0, problem, step_params).astype(f)
                )
            ).astype(dtype)
        # Layer 0 is *assigned from* the oracle, so its error is zero by
        # definition; the reference reads back the memory it just wrote and
        # reports exactly 0 (openmp_sol.cpp:126-133, 169-190).  Recomputing
        # the analytic product here and subtracting would measure XLA's FMA
        # rematerialization noise (~1 ulp), not solver error - u0's
        # correctness is pinned by tests/test_single_device.py instead.
        err_dtype = stencil_ref.compute_dtype(dtype)
        a0 = r0 = jnp.zeros((), err_dtype)
        if compute_errors:
            a1, r1 = errors(u1, 1)
        else:
            a1 = r1 = jnp.zeros((), err_dtype)

        (u_prev, u_cur), (abs_t, rel_t) = _scan_layers(
            problem, step, step_params, errors, compute_errors, dtype,
            u0, u1, 1, nsteps,
        )
        abs_all = jnp.concatenate([jnp.stack([a0, a1]), abs_t])
        rel_all = jnp.concatenate([jnp.stack([r0, r1]), rel_t])
        return u_prev, u_cur, abs_all, rel_all

    return jax.jit(run), step_params


def solve(
    problem: Problem,
    dtype=jnp.float32,
    step_fn: Optional[Callable] = None,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    phase: float = oracle.TWO_PI,
) -> SolveResult:
    """Compile + run, with the reference's two timing phases.

    "grids initialized in Xms" maps to compile time here (state allocation is
    part of the program); "numerical solution calculated in Xms" is the
    execution wall time (mpi_new.cpp:472-474, 354-357).
    """
    runner, step_params = make_solver(
        problem, dtype, step_fn, compute_errors, stop_step, phase
    )
    (u_prev, u_cur, abs_all, rel_all), init_s, solve_s = _timed_compile_run(
        runner, (step_params,), sync=lambda out: np.asarray(out[2]),
        path="leapfrog", scheme="standard", k=1, n=problem.N,
    )
    with tracing.span("solve.finish", path="leapfrog"):
        result = SolveResult(
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
        # A variable-c kernel arrives as a ParamStep (the field is a runtime
        # argument by construction), so field presence is detectable here -
        # the 1-step roofline model adds the field stream exactly when the
        # kernel reads one.
        obs_metrics.record_solve(
            result, "leapfrog",
            with_field=isinstance(step_fn, ParamStep),
        )
    return result


def make_compensated_solver(
    problem: Problem,
    dtype=jnp.float32,
    comp_step_fn: Optional[Callable] = None,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    phase: float = oracle.TWO_PI,
):
    """Jitted end-to-end solver on the compensated (Kahan) incremental
    scheme - see stencil_ref.compensated_step for the numerics and the
    measured ~7000x rounding reduction.

    `comp_step_fn(u, v, carry, problem, coeff) -> (u', v', carry')`
    defaults to the jnp-roll reference; the fused Pallas kernel slots in
    via `stencil_pallas.make_compensated_step_fn()`.  The scheme exists to
    push f32 to the discretization limit; bf16 state is rejected (its
    representation error alone dwarfs what compensation recovers).

    `phase` follows `make_solver`'s contract (lane identity in the
    ensemble engine): a shifted phase initializes layers 0/1 from the
    ANALYTIC solution, with v1 the exact analytic increment
    (`analytic_increment_layer1` - in exact arithmetic the next step
    then reproduces 2u1 - u0 + C lap(u1), the standard leapfrog update)
    and a zero Kahan carry; the reference phase keeps the step-derived
    half-step bootstrap bit-identically.
    """
    if dtype == jnp.bfloat16:
        raise ValueError(
            "compensated scheme requires f32/f64 state (bf16 representation "
            "error dominates anything the compensation recovers)"
        )
    step = (
        comp_step_fn if comp_step_fn is not None
        else stencil_ref.compensated_step
    )
    errors = _error_fn(problem, dtype, phase)
    analytic_bootstrap = phase != oracle.TWO_PI
    nsteps = problem.timesteps if stop_step is None else stop_step
    if not 1 <= nsteps <= problem.timesteps:
        raise ValueError(
            f"stop_step must be in [1, {problem.timesteps}], got {nsteps}"
        )

    def run():
        u0 = initial_layer0(problem, dtype, phase)
        if analytic_bootstrap:
            u1 = analytic_layer(problem, dtype, phase, 1)
            v1 = analytic_increment_layer1(problem, dtype, phase)
            c1 = jnp.zeros_like(u0)
        else:
            zero = jnp.zeros_like(u0)
            # Layer 1 = the same step with v = carry = 0 and coeff = C/2:
            # u1 = u0 + (C/2)lap(u0), the Taylor half-step, with v1/carry1
            # correctly primed for the loop.
            u1, v1, c1 = step(u0, zero, zero, problem, 0.5 * problem.a2tau2)
        a0 = r0 = jnp.zeros((), dtype)
        if compute_errors:
            a1, r1 = errors(u1, 1)
        else:
            a1 = r1 = jnp.zeros((), dtype)

        def body(carry, layer):
            u, v, c = carry
            u2, v2, c2 = step(u, v, c, problem, None)
            if compute_errors:
                ae, re = errors(u2, layer)
            else:
                ae = re = jnp.zeros((), dtype)
            return (u2, v2, c2), (ae, re)

        (u, v, c), (abs_t, rel_t) = jax.lax.scan(
            body, (u1, v1, c1), jnp.arange(2, nsteps + 1)
        )
        abs_all = jnp.concatenate([jnp.stack([a0, a1]), abs_t])
        rel_all = jnp.concatenate([jnp.stack([r0, r1]), rel_t])
        # u_prev reconstructed from the increment (v = u_n - u_{n-1}
        # exactly in exact arithmetic; here to f32 rounding) so the result
        # shape matches the standard solver's; v and carry ride along for
        # checkpointing.
        return u - v, u, v, c, abs_all, rel_all

    return jax.jit(run)


def solve_compensated(
    problem: Problem,
    dtype=jnp.float32,
    comp_step_fn: Optional[Callable] = None,
    compute_errors: bool = True,
    stop_step: Optional[int] = None,
    phase: float = oracle.TWO_PI,
) -> SolveResult:
    """Compile + run the compensated-scheme solve (see
    make_compensated_solver)."""
    runner = make_compensated_solver(
        problem, dtype, comp_step_fn, compute_errors, stop_step, phase
    )
    (u_prev, u_cur, v, carry, abs_all, rel_all), init_s, solve_s = (
        _timed_compile_run(
            runner, (), sync=lambda out: np.asarray(out[4]),
            path="compensated", scheme="compensated", k=1, n=problem.N,
        )
    )
    with tracing.span("solve.finish", path="compensated"):
        result = SolveResult(
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
            comp_v=v,
            comp_carry=carry,
        )
        obs_metrics.record_solve(result, "compensated", scheme="compensated")
    return result


def resume_compensated(
    problem: Problem,
    u_cur,
    v,
    carry,
    start_step: int,
    dtype=jnp.float32,
    comp_step_fn: Optional[Callable] = None,
    compute_errors: bool = True,
) -> SolveResult:
    """Re-enter the compensated scan at layer `start_step`.

    `(u_cur, v, carry)` is the full compensated state a checkpoint stored
    (SolveResult.u_cur / .comp_v / .comp_carry of a stopped run); the
    per-step op sequence equals an uninterrupted run's, so the final state
    is bitwise-equal (tests/test_compensated.py).
    """
    if dtype == jnp.bfloat16:
        raise ValueError("compensated scheme requires f32/f64 state")
    step = (
        comp_step_fn if comp_step_fn is not None
        else stencil_ref.compensated_step
    )
    nsteps = problem.timesteps
    if not 1 <= start_step <= nsteps:
        raise ValueError(
            f"start_step must be in [1, {nsteps}], got {start_step}"
        )
    errors = _error_fn(problem, dtype)

    def run(u_cur, v, carry):
        def body(state, layer):
            u, vv, cc = state
            u2, v2, c2 = step(u, vv, cc, problem, None)
            if compute_errors:
                ae, re = errors(u2, layer)
            else:
                ae = re = jnp.zeros((), dtype)
            return (u2, v2, c2), (ae, re)

        (u, vv, cc), (abs_t, rel_t) = jax.lax.scan(
            body, (u_cur, v, carry), jnp.arange(start_step + 1, nsteps + 1)
        )
        head = jnp.zeros((start_step + 1,), dtype)
        return (
            u - vv, u, vv, cc,
            jnp.concatenate([head, abs_t]),
            jnp.concatenate([head, rel_t]),
        )

    args = (
        jnp.asarray(u_cur, dtype),
        jnp.asarray(v, dtype),
        jnp.asarray(carry, dtype),
    )
    (u_prev, u, vv, cc, abs_all, rel_all), init_s, solve_s = (
        _timed_compile_run(
            jax.jit(run), args, sync=lambda out: np.asarray(out[4]),
            path="compensated", scheme="compensated", k=1, n=problem.N,
        )
    )
    return SolveResult(
        problem=problem,
        u_prev=u_prev,
        u_cur=u,
        abs_errors=np.asarray(abs_all, dtype=np.float64),
        rel_errors=np.asarray(rel_all, dtype=np.float64),
        init_seconds=init_s,
        solve_seconds=solve_s,
        steps_computed=nsteps - start_step,
        final_step=nsteps,
        comp_v=vv,
        comp_carry=cc,
    )


def resume(
    problem: Problem,
    u_prev,
    u_cur,
    start_step: int,
    dtype=jnp.float32,
    step_fn: Optional[Callable] = None,
    compute_errors: bool = True,
) -> SolveResult:
    """Re-enter the time loop at layer `start_step` and march to the end.

    `u_prev` / `u_cur` are layers start_step-1 / start_step (what
    `solve(stop_step=start_step)` returned and io/checkpoint.py stored).
    Because the per-step operation sequence is identical to an uninterrupted
    run's, the final state is bitwise-equal to it (pinned by
    tests/test_checkpoint.py).

    The returned error arrays cover layers start_step+1..timesteps; earlier
    entries are zero (they belong to the pre-checkpoint run's report).
    """
    step, step_params = _as_param_step(step_fn)
    nsteps = problem.timesteps
    if not 1 <= start_step <= nsteps:
        raise ValueError(
            f"start_step must be in [1, {nsteps}], got {start_step}"
        )
    errors = _error_fn(problem, dtype)

    def run(u_prev, u_cur, step_params):
        (u_p, u_c), (abs_t, rel_t) = _scan_layers(
            problem, step, step_params, errors, compute_errors, dtype,
            u_prev, u_cur, start_step, nsteps,
        )
        head = jnp.zeros((start_step + 1,), stencil_ref.compute_dtype(dtype))
        return (
            u_p,
            u_c,
            jnp.concatenate([head, abs_t]),
            jnp.concatenate([head, rel_t]),
        )

    args = (jnp.asarray(u_prev, dtype), jnp.asarray(u_cur, dtype), step_params)
    (u_p, u_c, abs_all, rel_all), init_s, solve_s = _timed_compile_run(
        jax.jit(run), args, sync=lambda out: np.asarray(out[2]),
        path="leapfrog", scheme="standard", k=1, n=problem.N,
    )
    return SolveResult(
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
    length: int = 1,
    step_fn: Optional[Callable] = None,
    compute_errors: bool = True,
):
    """Fixed-length re-entry program for supervised solves (run/supervisor).

    Returns `(runner, step_params)`; `runner(u_prev, u_cur, start,
    step_params)` marches layers start+1..start+length with `start` a
    RUNTIME scalar, so one compiled program serves every equal-length
    chunk of a supervised march - no per-chunk retracing.  The scan body
    is `_scan_layers_xs`, the same one `solve`/`resume` run, so chunked
    layers are bitwise-identical to an uninterrupted march's.  Error
    outputs cover exactly the chunk's layers (the supervisor assembles
    the full per-layer vectors on host).
    """
    if length < 1:
        raise ValueError(f"chunk length must be >= 1, got {length}")
    step, step_params = _as_param_step(step_fn)
    errors = _error_fn(problem, dtype)

    def run(u_prev, u_cur, start, step_params):
        xs = start + 1 + jnp.arange(length, dtype=jnp.int32)
        (u_p, u_c), (abs_t, rel_t) = _scan_layers_xs(
            problem, step, step_params, errors, compute_errors, dtype,
            u_prev, u_cur, xs,
        )
        return u_p, u_c, abs_t, rel_t

    return jax.jit(run), step_params


def make_comp_chunk_runner(
    problem: Problem,
    dtype=jnp.float32,
    length: int = 1,
    comp_step_fn: Optional[Callable] = None,
    compute_errors: bool = True,
):
    """Compensated-scheme counterpart of `make_chunk_runner`:
    `runner(u, v, carry, start)` marches `length` layers from the
    compensated state with a runtime `start` - the same scan body as
    `resume_compensated`, compiled once per chunk length."""
    if length < 1:
        raise ValueError(f"chunk length must be >= 1, got {length}")
    if dtype == jnp.bfloat16:
        raise ValueError("compensated scheme requires f32/f64 state")
    step = (
        comp_step_fn if comp_step_fn is not None
        else stencil_ref.compensated_step
    )
    errors = _error_fn(problem, dtype)

    def run(u_cur, v, carry, start):
        def body(state, layer):
            u, vv, cc = state
            u2, v2, c2 = step(u, vv, cc, problem, None)
            if compute_errors:
                ae, re = errors(u2, layer)
            else:
                ae = re = jnp.zeros((), dtype)
            return (u2, v2, c2), (ae, re)

        xs = start + 1 + jnp.arange(length, dtype=jnp.int32)
        (u, vv, cc), (abs_t, rel_t) = jax.lax.scan(
            body, (u_cur, v, carry), xs
        )
        return u, vv, cc, abs_t, rel_t

    return jax.jit(run)


def solve_history(problem: Problem, dtype=jnp.float32) -> np.ndarray:
    """Full time history (timesteps+1, N, N, N) - the openmp_sol storage model.

    The reference OpenMP/mpi_sol variants keep every layer in memory and
    compute errors post hoc (openmp_sol.cpp:216-219, 169-190).  Provided for
    parity testing and small-N debugging; O(T * N^3) memory.
    """

    @jax.jit
    def run():
        u0, u1 = initial_state(problem, dtype)

        def body(carry, _):
            u_prev, u = carry
            u_next = stencil_ref.leapfrog_step(u_prev, u, problem)
            return (u, u_next), u_next

        _, rest = jax.lax.scan(
            body, (u0, u1), None, length=problem.timesteps - 1
        )
        return jnp.concatenate([jnp.stack([u0, u1]), rest])

    return np.asarray(run())


def to_reference_grid(u: np.ndarray) -> np.ndarray:
    """Expand a fundamental-domain (N,N,N) field to the reference's (N+1)^3.

    Re-attaches the duplicated periodic seam plane x=N (= x=0) and the zero
    Dirichlet planes y=N, z=N, giving index-for-index comparability with the
    reference's `Grid` layout (openmp_sol.cpp:44-50).
    """
    u = np.asarray(u)
    n = u.shape[0]
    out = np.zeros((n + 1, n + 1, n + 1), dtype=u.dtype)
    out[:n, :n, :n] = u
    out[n, :n, :n] = u[0]
    return out
