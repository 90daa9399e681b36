"""Vmapped ensemble core: a batch of independent solves as one program.

A batch (an "ensemble") shares the compiled-program identity - (N, Lx/y/z,
T, timesteps, scheme, kernel path, k, dtype, batch size) - while each LANE
differs in

 * the initial time phase of the analytic solution (`LaneSpec.phase`;
   u(0) = Sx*Sy*Sz * cos(phase), which solves the PDE for any phase, so
   the per-lane error oracle stays exact),
 * the number of layers marched (`LaneSpec.stop_step`: the batch marches
   to the max and earlier-stopping lanes are FROZEN by `where` masking,
   which preserves their state bit-for-bit; a standard 1-step batch whose
   every lane runs to `timesteps` marches without the mask), and
 * optionally a per-lane tau^2 c^2(x,y,z) field (no analytic oracle, so
   field batches require compute_errors=False).

Wired paths: "roll" (the jnp stencil), "pallas" (the fused 1-step slab
kernel), "kfused" (the k-step onion, k >= 2) - each on BOTH schemes:
"standard" mirrors leapfrog.make_solver / kfused.make_kfused_solver, and
"compensated" (the flagship Kahan velocity form) mirrors
leapfrog.make_compensated_solver / kfused_comp.make_kfused_comp_solver
(the `fused_kstep_comp` onion for k >= 2).  Each lane's op sequence
inside the vmapped program mirrors the corresponding solo solver's op
for op - the BITWISE lane-parity contract is pinned by
tests/test_ensemble.py, and any change here or there must keep that
suite green.  Compensated batches are constant-speed only (the solo
velocity-form field path exists, but per-lane fields are not wired
through the compensated vmapped core).

Every batch runs as the vmapped program: a program that cannot be built
or run raises, and the caller (the serve engine's breaker, a request's
error answer) sees the error.

Per-lane timestep masking on the "kfused" path freezes whole k-blocks, so
a lane's stop_step must sit on the block grid ((stop-1) % k == 0) or be
the full march; the 1-step paths mask per layer.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import List, Optional, Sequence, Tuple

import numpy as np

from wavetpu.core.problem import Problem
from wavetpu.obs import tracing
from wavetpu.verify import oracle

PATHS = ("roll", "pallas", "kfused")
SCHEMES = ("standard", "compensated")


@dataclasses.dataclass(frozen=True)
class LaneSpec:
    """One lane of an ensemble batch.

    `phase`: initial time phase of the analytic solution (reference: 2*pi).
    `stop_step`: layers to march (None = the problem's timesteps; the lane
    freezes there while the batch marches on).  `c2tau2_field`: optional
    host (N,N,N) tau^2 c^2 array (stencil_ref.make_c2tau2_field).
    """

    phase: float = oracle.TWO_PI
    stop_step: Optional[int] = None
    c2tau2_field: Optional[object] = None

    def stop(self, problem: Problem) -> int:
        return (
            problem.timesteps if self.stop_step is None else self.stop_step
        )


def padding_lane(stop_step: int = 1) -> LaneSpec:
    """The filler lane batches are padded with: default phase, frozen
    after layer `stop_step` (1, the default, sits on every k-block grid).
    `solve_ensemble` passes the batch's largest real stop, so padding
    never asks for a mask the real lanes do not (a batch of full-stop
    requests marches unmasked).  Padding lanes ride the batch axis only -
    elementwise across lanes - so real lanes are bitwise unchanged
    (tests/test_ensemble.py pins it).
    """
    return LaneSpec(stop_step=stop_step)


@dataclasses.dataclass
class EnsembleResult:
    """A batched solve's outcome: per-lane SolveResults + how it ran.

    `batch_size` counts the compiled program's lanes including padding,
    `n_lanes` the real ones.  `solve_seconds` is the whole batch's wall
    time (each lane's SolveResult carries the same number: lanes finish
    together).  `masked` is True when the program froze lanes with
    per-step `where` selects (`EnsembleSolver.masked`); False for an
    unmasked march.
    """

    problem: Problem
    results: List["SolveResult"]  # noqa: F821 - from solver.leapfrog
    path: str
    batch_size: int
    n_lanes: int
    init_seconds: float
    solve_seconds: float
    # The raw (B, N, N, N) batched state (padding lanes included).  The
    # serve engine's per-lane watchdog reduces over these directly -
    # re-stacking the per-lane views would copy the whole batch state
    # per request batch.
    u_prev_batch: object
    u_cur_batch: object
    masked: bool

    @property
    def aggregate_gcells_per_second(self) -> float:
        """Sum of per-lane cell-updates over the batch wall time - the
        serving throughput number (arXiv:2108.11076's batching win)."""
        if not self.solve_seconds:
            return 0.0
        total = sum(
            self.problem.cells_per_step * (r.steps_computed or 0)
            for r in self.results
        )
        return total / self.solve_seconds / 1e9


def _validate(problem: Problem, lanes: Sequence[LaneSpec], path: str,
              k: int, compute_errors: bool,
              scheme: str = "standard") -> bool:
    """Shared lane validation; returns with_field (all-or-none normalized
    by the caller via `fill_fields`)."""
    if path not in PATHS:
        raise ValueError(f"path must be one of {PATHS}, got {path!r}")
    if scheme not in SCHEMES:
        raise ValueError(
            f"scheme must be one of {SCHEMES}, got {scheme!r}"
        )
    if not lanes:
        raise ValueError("an ensemble needs at least one lane")
    if scheme == "compensated" and any(
        lane.c2tau2_field is not None for lane in lanes
    ):
        raise ValueError(
            "per-lane c2tau2 fields are not wired through the compensated "
            "vmapped core; use scheme='standard' for field batches"
        )
    if path == "kfused":
        if k < 2:
            raise ValueError(f"kfused path needs k >= 2, got {k}")
        if problem.N % k:
            raise ValueError(f"k={k} must divide N={problem.N}")
    with_field = any(lane.c2tau2_field is not None for lane in lanes)
    if with_field and compute_errors:
        raise ValueError(
            "per-lane c2tau2 fields have no analytic oracle; pass "
            "compute_errors=False"
        )
    for i, lane in enumerate(lanes):
        s = lane.stop(problem)
        if not 1 <= s <= problem.timesteps:
            raise ValueError(
                f"lane {i}: stop_step must be in [1, {problem.timesteps}],"
                f" got {s}"
            )
        if path == "kfused" and s != problem.timesteps and (s - 1) % k:
            raise ValueError(
                f"lane {i}: on the kfused path a lane freezes at whole "
                f"k-blocks - stop_step must satisfy (stop-1) % {k} == 0 "
                f"or equal timesteps={problem.timesteps}, got {s}"
            )
        if lane.c2tau2_field is not None and np.shape(
            lane.c2tau2_field
        ) != (problem.N,) * 3:
            raise ValueError(
                f"lane {i}: c2tau2_field shape "
                f"{np.shape(lane.c2tau2_field)} != {(problem.N,) * 3}"
            )
        if with_field and lane.phase != oracle.TWO_PI:
            # A shifted phase bootstraps layer 1 from the ANALYTIC
            # solution, which only exists for constant speed - and in a
            # field batch EVERY lane runs the variable-c kernel
            # (fill_fields), so the whole batch must keep the reference
            # phase.  (The serve scheduler never mixes these anyway:
            # field presence is part of the bucket key.)
            raise ValueError(
                f"lane {i}: a shifted phase has no analytic layer-1 "
                f"bootstrap in a variable-c field batch; use the "
                f"reference phase with c2tau2_field"
            )
    return with_field


def fill_fields(problem: Problem, lanes: Sequence[LaneSpec]) -> list:
    """In a field batch every lane runs the variable-c kernel, so lanes
    without a field get the CONSTANT tau^2 a^2 field (numerically the
    constant-speed problem; bitwise it matches the solo variable-c solve
    with that constant field, not the constant-c kernel - documented in
    docs/serving.md)."""
    const = None
    out = []
    for lane in lanes:
        if lane.c2tau2_field is None:
            if const is None:
                const = np.full(
                    (problem.N,) * 3, problem.a2tau2, dtype=np.float64
                )
            lane = dataclasses.replace(lane, c2tau2_field=const)
        out.append(lane)
    return out


def _lane_error_fn(problem: Problem, dtype):
    """(u, n, ct_table) -> (abs_e, rel_e): leapfrog._error_fn with the
    time-factor table as a runtime argument instead of a closed-over
    constant (per-lane tables ride the batch axis).  Must stay op-for-op
    identical to leapfrog._error_fn for the bitwise parity contract."""
    import jax.numpy as jnp

    from wavetpu.kernels import stencil_ref

    f_dtype = stencil_ref.compute_dtype(dtype)
    sx, sy, sz = oracle.spatial_factors(problem, f_dtype)
    mask = jnp.asarray(oracle.interior_masks_1d(problem.N))

    def errors(u, n, ct_table):
        fld = oracle.analytic_field(sx, sy, sz, ct_table[n])
        return oracle.layer_errors(u.astype(f_dtype), fld, mask, mask, mask)

    return errors


def _lane_error_fn_guarded(problem: Problem, dtype):
    """`_lane_error_fn` with the representation-zero sx planes excluded
    from the REL metric - the runtime-ct-table twin of
    kfused_comp._error_fn_guarded (the velocity-form onion's bootstrap-
    layer metric).  Must stay op-for-op identical to it."""
    import jax.numpy as jnp

    from wavetpu.kernels import stencil_ref
    from wavetpu.solver import kfused_comp

    f_dtype = stencil_ref.compute_dtype(dtype)
    sx, sy, sz = oracle.spatial_factors(problem, f_dtype)
    mask = jnp.asarray(oracle.interior_masks_1d(problem.N))
    mask_x = mask & (jnp.abs(sx) > kfused_comp._rel_guard_tol(f_dtype))

    def errors(u, n, ct_table):
        fld = oracle.analytic_field(sx, sy, sz, ct_table[n])
        return oracle.layer_errors(
            u.astype(f_dtype), fld, mask_x, mask, mask
        )

    return errors


def _comp_bootstrap(problem: Problem, dtype, v_dtype, carry_dtype, sx, sy,
                    sz, ct_table, taylor, comp_step):
    """Compensated layers 0/1 from a runtime ct table.

    The per-lane `taylor` selector mirrors the solo compensated solvers'
    STATIC phase decision: True = the compensated half-step bootstrap
    (v = carry = 0, coeff = C/2 - leapfrog.make_compensated_solver /
    kfused_comp._bootstrap), False = the exact analytic two-level
    initialization shifted phases take (u0/u1 analytic, v1 the exact
    analytic increment Sx Sy Sz (ct1 - ct0) - a pure product, matching
    leapfrog.analytic_increment_layer1; the u1 - u0 form FMA-contracts
    differently between program shapes).  Both branches mirror the
    corresponding solo program op for op; `where` selects bitwise.
    """
    import jax.numpy as jnp

    from wavetpu.kernels import stencil_ref

    u0 = stencil_ref.apply_dirichlet(
        oracle.analytic_field(sx, sy, sz, ct_table[0])
    ).astype(dtype)
    zero = jnp.zeros_like(u0)
    u1_s, v1_s, c1_s = comp_step(
        u0, zero, zero, problem, 0.5 * problem.a2tau2
    )
    v1_s = v1_s.astype(v_dtype)
    c1_s = c1_s.astype(carry_dtype)
    u1_a = stencil_ref.apply_dirichlet(
        oracle.analytic_field(sx, sy, sz, ct_table[1])
    ).astype(dtype)
    v1_a = stencil_ref.apply_dirichlet(
        oracle.analytic_field(sx, sy, sz, ct_table[1] - ct_table[0])
    ).astype(v_dtype)
    c1_a = jnp.zeros(u0.shape, carry_dtype)
    return (
        jnp.where(taylor, u1_s, u1_a),
        jnp.where(taylor, v1_s, v1_a),
        jnp.where(taylor, c1_s, c1_a),
    )


def _comp_step1(path: str, block_x, interpret):
    """The batch's 1-step compensated kernel
    `(u, v, carry, problem, coeff) -> (u', v', carry')`: the jnp-roll
    reference on the "roll" path, the fused Pallas kernel elsewhere
    (the "kfused" lane bootstraps through the same Pallas 1-step kernel
    the solo velocity-form onion does)."""
    from wavetpu.kernels import stencil_pallas, stencil_ref

    if path == "roll":
        return stencil_ref.compensated_step
    if path == "pallas":
        return stencil_pallas.make_compensated_step_fn(
            block_x=block_x, interpret=interpret
        )

    def step(u, v, carry, problem, coeff):
        return stencil_pallas.compensated_step(
            u, v, carry, problem, coeff, interpret=interpret
        )

    return step


def _bootstrap(problem: Problem, dtype, sx, sy, sz, ct_table, taylor,
               step, params):
    """Layers 0/1 from a runtime ct table.

    `taylor` is the lane's per-lane bootstrap selector: True = the
    reference's step-derived Taylor half-step (valid only at the
    reference phase, where u_t(0) = 0), False = the exact analytic
    layer-1 initialization shifted phases need (see
    leapfrog.make_solver).  The `where` reproduces the solo solver's
    STATIC phase decision at runtime, selecting bitwise between two
    branches that each mirror the corresponding solo program op for op.
    """
    import jax.numpy as jnp

    from wavetpu.kernels import stencil_ref

    f = stencil_ref.compute_dtype(dtype)
    u0 = stencil_ref.apply_dirichlet(
        oracle.analytic_field(sx, sy, sz, ct_table[0])
    ).astype(dtype)
    u1_step = (
        0.5 * (u0.astype(f) + step(u0, u0, problem, params).astype(f))
    ).astype(dtype)
    u1_analytic = stencil_ref.apply_dirichlet(
        oracle.analytic_field(sx, sy, sz, ct_table[1])
    ).astype(dtype)
    return u0, jnp.where(taylor, u1_step, u1_analytic)


def _step1_pair(problem: Problem, path: str, block_x, interpret,
                with_field):
    """(fn4, default_params) for the batch's 1-step kernel: the roll or
    pallas step in leapfrog's 4-arg ParamStep form.  For field batches the
    fn takes the per-lane field as its params argument (the throwaway
    ParamStep built here only donates its .fn; its dummy params are never
    used)."""
    from wavetpu.kernels import stencil_ref
    from wavetpu.solver import leapfrog

    if path == "roll":
        if with_field:
            return stencil_ref.make_variable_c_step(
                np.zeros((1, 1, 1))
            ).fn, ()
        return leapfrog._as_param_step(None)
    from wavetpu.kernels import stencil_pallas

    if with_field:
        return stencil_pallas.make_step_fn(
            block_x=block_x, interpret=interpret,
            c2tau2_field=np.zeros((1, 1, 1)),
        ).fn, ()
    return leapfrog._as_param_step(
        stencil_pallas.make_step_fn(block_x=block_x, interpret=interpret)
    )


class EnsembleSolver:
    """The compiled batched program for one (problem, path, batch) key.

    Built once, reused across batches - this is the object the serve
    layer's program cache holds.  `compile()` ahead-of-time lowers the
    vmapped march (warm-up without executing a solve); `run(lanes)`
    executes it on a packed batch and returns per-lane SolveResults.

    The lane program vmapped here mirrors the solo solver's op sequence
    exactly; tests/test_ensemble.py pins bitwise lane parity.  Lanes that
    stop early are frozen by per-step `where` masks; the standard 1-step
    program skips them when every lane, padding included, runs to
    `timesteps` (`masked`).
    """

    def __init__(
        self,
        problem: Problem,
        n_lanes: int,
        dtype=None,
        path: str = "roll",
        k: int = 4,
        compute_errors: bool = True,
        interpret: Optional[bool] = None,
        block_x: Optional[int] = None,
        with_field: bool = False,
        scheme: str = "standard",
    ):
        import jax
        import jax.numpy as jnp

        from wavetpu.kernels import stencil_ref

        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        if path not in PATHS:
            raise ValueError(f"path must be one of {PATHS}, got {path!r}")
        if scheme not in SCHEMES:
            raise ValueError(
                f"scheme must be one of {SCHEMES}, got {scheme!r}"
            )
        if path == "kfused":
            if k < 2:
                raise ValueError(f"kfused path needs k >= 2, got {k}")
            if problem.N % k:
                raise ValueError(f"k={k} must divide N={problem.N}")
        if with_field and compute_errors:
            raise ValueError(
                "field batches have no analytic oracle; pass "
                "compute_errors=False"
            )
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.problem = problem
        self.n_lanes = n_lanes
        self.dtype = jnp.float32 if dtype is None else dtype
        self.path = path
        self.k = k if path == "kfused" else 1
        self.compute_errors = compute_errors
        self.with_field = with_field
        self.scheme = scheme
        if scheme == "compensated":
            if with_field:
                raise ValueError(
                    "per-lane c2tau2 fields are not wired through the "
                    "compensated vmapped core"
                )
            if jnp.dtype(self.dtype) == jnp.bfloat16:
                raise ValueError(
                    "compensated scheme requires f32/f64 state"
                )
        self._f = stencil_ref.compute_dtype(self.dtype)
        self._exec = None
        self.compile_seconds: Optional[float] = None
        if scheme == "compensated":
            lane_run = (
                self._comp_kfused_lane(interpret, block_x)
                if path == "kfused"
                else self._comp_onestep_lane(interpret, block_x)
            )
        else:
            lane_run = (
                self._kfused_lane(interpret, block_x)
                if path == "kfused"
                else self._onestep_lane(interpret, block_x)
            )
        in_axes = (0, 0, 0, 0) if with_field else (0, 0, 0)
        if self._may_skip_mask:
            # The batch-level "every lane runs to the last layer" flag
            # rides unbatched (in_axes None), so vmap keeps the lane
            # program's lax.cond a real conditional.
            lanes_run = jax.vmap(lane_run, in_axes=(None,) + in_axes)
            last = problem.timesteps

            def batch_run(cts, stops, taylors, *field):
                return lanes_run(
                    jnp.all(stops == last), cts, stops, taylors, *field
                )

            self._runner = jax.jit(batch_run)
        else:
            self._runner = jax.jit(jax.vmap(lane_run, in_axes=in_axes))

    @property
    def _may_skip_mask(self) -> bool:
        """Only the standard 1-step lane program has an unmasked march."""
        return self.scheme == "standard" and self.path != "kfused"

    def masked(self, lanes: Sequence[LaneSpec]) -> bool:
        """Whether this (padded) batch marches with the per-step lane
        masks: the predicate the program itself branches on."""
        return not self._may_skip_mask or any(
            lane.stop(self.problem) != self.problem.timesteps
            for lane in lanes
        )

    # ---- lane programs (solo op sequences with runtime ct tables) ----

    def _onestep_lane(self, interpret, block_x):
        import jax.numpy as jnp
        from jax import lax

        problem, dtype, f = self.problem, self.dtype, self._f
        compute_errors = self.compute_errors
        sx, sy, sz = oracle.spatial_factors(problem, f)
        errors = _lane_error_fn(problem, dtype)
        step, params0 = _step1_pair(
            problem, self.path, block_x, interpret, self.with_field
        )

        def lane_run(full, ct_table, stop, taylor, *field):
            params = field[0] if self.with_field else params0

            def layer(u_prev, u, n):
                u_next = step(u_prev, u, problem, params)
                if compute_errors:
                    return u_next, errors(u_next, n, ct_table)
                return u_next, (jnp.zeros((), f), jnp.zeros((), f))

            def masked_body(carry, n):
                u_prev, u = carry
                u_next, (ae, re) = layer(u_prev, u, n)
                live = n <= stop
                if compute_errors:
                    ae = jnp.where(live, ae, jnp.zeros((), f))
                    re = jnp.where(live, re, jnp.zeros((), f))
                return (
                    jnp.where(live, u, u_prev),
                    jnp.where(live, u_next, u),
                ), (ae, re)

            def full_body(carry, n):
                u_prev, u = carry
                u_next, rows = layer(u_prev, u, n)
                return (u, u_next), rows

            def march(body, unroll):
                u0, u1 = _bootstrap(
                    problem, dtype, sx, sy, sz, ct_table, taylor, step,
                    params,
                )
                a0 = r0 = jnp.zeros((), f)
                if compute_errors:
                    a1, r1 = errors(u1, 1, ct_table)
                else:
                    a1 = r1 = jnp.zeros((), f)
                (u_prev, u_cur), (abs_t, rel_t) = lax.scan(
                    body, (u0, u1), jnp.arange(2, problem.timesteps + 1),
                    unroll=unroll,
                )
                return (
                    u_prev,
                    u_cur,
                    jnp.concatenate([jnp.stack([a0, a1]), abs_t]),
                    jnp.concatenate([jnp.stack([r0, r1]), rel_t]),
                )

            # One branch a batch, not one select a step: when every lane
            # runs to the last layer none freezes, and the selects (three
            # fields read and two written a step) only copy.  The full
            # march takes three layers a loop turn: a step reads two
            # fields and writes a third, so the state cycles through
            # three buffers, and with fewer steps a turn XLA copies both
            # fields back into the loop's own buffers every turn.  Each
            # branch bootstraps its own layers 0/1: a loop started from
            # the conditional's operands copied the masked march's state
            # every step.  Same kernel, operands and order in both
            # branches, so every lane stays bitwise what the masked march
            # gives.
            return lax.cond(
                full,
                lambda: march(full_body, 3),
                lambda: march(masked_body, 1),
            )

        return lane_run

    def _kfused_lane(self, interpret, block_x):
        import jax.numpy as jnp
        from jax import lax

        from wavetpu.kernels import stencil_pallas
        from wavetpu.solver import kfused, leapfrog

        problem, dtype, f = self.problem, self.dtype, self._f
        k, compute_errors = self.k, self.compute_errors
        sx, _ct, syz, rsyz, xmask, inv_absx = kfused._oracle_parts(
            problem, f
        )
        _, sy, sz = oracle.spatial_factors(problem, f)
        errors = _lane_error_fn(problem, dtype)
        step1, params0 = _step1_pair(
            problem, "pallas", block_x, interpret, self.with_field
        )
        nsteps = problem.timesteps
        nblocks = (nsteps - 1) // k
        rem = (nsteps - 1) - nblocks * k

        def lane_run(ct_table, stop, taylor, *field):
            params = field[0] if self.with_field else params0
            u0, u1 = _bootstrap(
                problem, dtype, sx, sy, sz, ct_table, taylor, step1, params
            )
            a0 = r0 = jnp.zeros((), f)
            if compute_errors:
                a1, r1 = errors(u1, 1, ct_table)
            else:
                a1 = r1 = jnp.zeros((), f)

            def kblock(carry, nstart):
                u_prev, u = carry
                ctk = lax.dynamic_slice(ct_table, (nstart + 1,), (k,))
                sxct = ctk[:, None] * sx[None, :]
                up, uc, dmax, rmax = stencil_pallas.fused_kstep(
                    u_prev, u, syz, rsyz, sxct,
                    k=k, coeff=problem.a2tau2, inv_h2=problem.inv_h2,
                    c2tau2_field=field[0] if self.with_field else None,
                    block_x=block_x, interpret=interpret,
                    with_errors=compute_errors,
                )
                if compute_errors:
                    abs_e, rel_e = kfused._block_errors(
                        dmax, rmax, ctk, xmask, inv_absx
                    )
                else:
                    abs_e = rel_e = jnp.zeros((k,), f)
                # A lane freezes at whole blocks: live iff the block's
                # last layer is within the lane's march.
                live = nstart + k <= stop
                return (
                    jnp.where(live, up, u_prev),
                    jnp.where(live, uc, u),
                ), (
                    jnp.where(live, abs_e, jnp.zeros((k,), f)),
                    jnp.where(live, rel_e, jnp.zeros((k,), f)),
                )

            starts = 1 + k * jnp.arange(nblocks)
            (u_prev, u_cur), (abs_b, rel_b) = lax.scan(
                kblock, (u0, u1), starts
            )
            abs_parts = [abs_b.reshape(-1)]
            rel_parts = [rel_b.reshape(-1)]
            if rem:
                # The uniform remainder tail marches the 1-step kernel,
                # masked per layer (as the solo kfused march's tail would,
                # for lanes stopping before it).
                def body(carry, n):
                    u_prev, u = carry
                    u_next = step1(u_prev, u, problem, params)
                    live = n <= stop
                    if compute_errors:
                        ae, re = errors(u_next, n, ct_table)
                        ae = jnp.where(live, ae, jnp.zeros((), f))
                        re = jnp.where(live, re, jnp.zeros((), f))
                    else:
                        ae = re = jnp.zeros((), f)
                    return (
                        jnp.where(live, u, u_prev),
                        jnp.where(live, u_next, u),
                    ), (ae, re)

                (u_prev, u_cur), (ra, rr) = lax.scan(
                    body, (u_prev, u_cur),
                    nsteps - rem + 1 + jnp.arange(rem, dtype=jnp.int32),
                )
                abs_parts.append(ra)
                rel_parts.append(rr)
            return (
                u_prev,
                u_cur,
                jnp.concatenate(
                    [jnp.stack([a0, a1])] + abs_parts
                ),
                jnp.concatenate(
                    [jnp.stack([r0, r1])] + rel_parts
                ),
            )

        return lane_run

    def _comp_onestep_lane(self, interpret, block_x):
        """Compensated (Kahan) 1-step lane: mirrors
        leapfrog.make_compensated_solver op for op with a runtime ct
        table (roll = stencil_ref.compensated_step, pallas = the fused
        Pallas compensated kernel)."""
        import jax.numpy as jnp
        from jax import lax

        problem, dtype, f = self.problem, self.dtype, self._f
        compute_errors = self.compute_errors
        sx, sy, sz = oracle.spatial_factors(problem, f)
        errors = _lane_error_fn(problem, dtype)
        step = _comp_step1(self.path, block_x, interpret)

        def lane_run(ct_table, stop, taylor):
            u1, v1, c1 = _comp_bootstrap(
                problem, dtype, dtype, dtype, sx, sy, sz, ct_table,
                taylor, step,
            )
            a0 = r0 = jnp.zeros((), f)
            if compute_errors:
                a1, r1 = errors(u1, 1, ct_table)
            else:
                a1 = r1 = jnp.zeros((), f)

            def body(carry, n):
                u, v, c = carry
                u2, v2, c2 = step(u, v, c, problem, None)
                live = n <= stop
                if compute_errors:
                    ae, re = errors(u2, n, ct_table)
                    ae = jnp.where(live, ae, jnp.zeros((), f))
                    re = jnp.where(live, re, jnp.zeros((), f))
                else:
                    ae = re = jnp.zeros((), f)
                return (
                    jnp.where(live, u2, u),
                    jnp.where(live, v2, v),
                    jnp.where(live, c2, c),
                ), (ae, re)

            (u, v, c), (abs_t, rel_t) = lax.scan(
                body, (u1, v1, c1), jnp.arange(2, problem.timesteps + 1)
            )
            # u_prev reconstructed from the increment, as the solo
            # compensated solver returns it.
            return (
                u - v,
                u,
                jnp.concatenate([jnp.stack([a0, a1]), abs_t]),
                jnp.concatenate([jnp.stack([r0, r1]), rel_t]),
            )

        return lane_run

    def _comp_kfused_lane(self, interpret, block_x):
        """Velocity-form compensated onion lane: mirrors
        kfused_comp._make_march (k-fused blocks + a k=1 tail through the
        SAME kernel) with a runtime ct table, per-lane k-block stop
        masking on (u, v, carry), and the guarded rel metric."""
        import jax.numpy as jnp
        from jax import lax

        from wavetpu.kernels import stencil_pallas
        from wavetpu.solver import kfused, kfused_comp

        problem, dtype, f = self.problem, self.dtype, self._f
        k, compute_errors = self.k, self.compute_errors
        v_dtype = dtype
        carry_dtype = kfused_comp._default_carry_dtype(dtype)
        sx, _ct, syz, rsyz, xmask, inv_absx = kfused._oracle_parts(
            problem, f
        )
        inv_absx = jnp.where(
            jnp.abs(sx) > kfused_comp._rel_guard_tol(f), inv_absx,
            jnp.asarray(0.0, f),
        )
        _, sy, sz = oracle.spatial_factors(problem, f)
        errors1 = _lane_error_fn_guarded(problem, dtype)
        step1 = _comp_step1("kfused", block_x, interpret)
        nsteps = problem.timesteps
        nblocks = (nsteps - 1) // k
        rem = (nsteps - 1) - nblocks * k

        def kblock(u, v, c, ct_table, nstart, kk, bxo):
            ctk = lax.dynamic_slice(ct_table, (nstart + 1,), (kk,))
            sxct = ctk[:, None] * sx[None, :]
            u2, v2, c2, dmax, rmax = stencil_pallas.fused_kstep_comp(
                u, v, c, syz, rsyz, sxct,
                k=kk, coeff=problem.a2tau2, inv_h2=problem.inv_h2,
                block_x=bxo, interpret=interpret,
                with_errors=compute_errors,
            )
            if compute_errors:
                abs_e, rel_e = kfused._block_errors(
                    dmax, rmax, ctk, xmask, inv_absx
                )
            else:
                abs_e = rel_e = jnp.zeros((kk,), f)
            return u2, v2, c2, abs_e, rel_e

        def lane_run(ct_table, stop, taylor):
            u1, v1, c1 = _comp_bootstrap(
                problem, dtype, v_dtype, carry_dtype, sx, sy, sz,
                ct_table, taylor, step1,
            )
            a0 = r0 = jnp.zeros((), f)
            if compute_errors:
                a1, r1 = errors1(u1, 1, ct_table)
            else:
                a1 = r1 = jnp.zeros((), f)

            def body(state, nstart):
                u, v, c = state
                u2, v2, c2, abs_e, rel_e = kblock(
                    u, v, c, ct_table, nstart, k, block_x
                )
                live = nstart + k <= stop
                return (
                    jnp.where(live, u2, u),
                    jnp.where(live, v2, v),
                    jnp.where(live, c2, c),
                ), (
                    jnp.where(live, abs_e, jnp.zeros((k,), f)),
                    jnp.where(live, rel_e, jnp.zeros((k,), f)),
                )

            starts = 1 + k * jnp.arange(nblocks)
            (u, v, c), (abs_b, rel_b) = lax.scan(
                body, (u1, v1, c1), starts
            )
            abs_parts = [abs_b.reshape(-1)]
            rel_parts = [rel_b.reshape(-1)]
            for t in range(rem):
                # The solo march's remainder: the same kernel at k=1
                # (kfused_comp._make_march), masked per layer here.
                u2, v2, c2, a_1, r_1 = kblock(
                    u, v, c, ct_table, nsteps - rem + t, 1, None
                )
                live = nsteps - rem + t + 1 <= stop
                u = jnp.where(live, u2, u)
                v = jnp.where(live, v2, v)
                c = jnp.where(live, c2, c)
                abs_parts.append(
                    jnp.where(live, a_1, jnp.zeros((1,), f))
                )
                rel_parts.append(
                    jnp.where(live, r_1, jnp.zeros((1,), f))
                )
            # u_prev as kfused_comp._as_result reconstructs it.
            return (
                (u.astype(f) - v.astype(f)).astype(dtype),
                u,
                jnp.concatenate([jnp.stack([a0, a1])] + abs_parts),
                jnp.concatenate([jnp.stack([r0, r1])] + rel_parts),
            )

        return lane_run

    # ---- packing / compiling / running ----

    def pack(self, lanes: Sequence[LaneSpec]) -> Tuple:
        """Device arguments for a padded batch: (B, T+1) ct tables, (B,)
        stop layers, and (B, N, N, N) fields when the batch carries them
        (caller has already run `fill_fields`)."""
        import jax.numpy as jnp

        if len(lanes) != self.n_lanes:
            raise ValueError(
                f"batch has {len(lanes)} lanes; this program wants "
                f"{self.n_lanes} (pad with padding_lane())"
            )
        cts = np.stack(
            [
                oracle.time_factor_table_np(self.problem, lane.phase)
                for lane in lanes
            ]
        )
        stops = np.asarray(
            [lane.stop(self.problem) for lane in lanes], np.int32
        )
        # Per-lane bootstrap selector: the solo solvers' STATIC
        # phase-equality decision, evaluated at pack time (see
        # _bootstrap).
        taylor = np.asarray(
            [lane.phase == oracle.TWO_PI for lane in lanes], bool
        )
        args = (
            jnp.asarray(cts, self._f),
            jnp.asarray(stops),
            jnp.asarray(taylor),
        )
        if self.with_field:
            fields = np.stack(
                [np.asarray(lane.c2tau2_field) for lane in lanes]
            )
            args = args + (jnp.asarray(fields, self._f),)
        return args

    def _example_args(self) -> Tuple:
        import jax.numpy as jnp

        b, t = self.n_lanes, self.problem.timesteps
        args = (
            jnp.zeros((b, t + 1), self._f),
            jnp.ones((b,), jnp.int32),
            jnp.ones((b,), bool),
        )
        if self.with_field:
            args = args + (jnp.zeros((b,) + (self.problem.N,) * 3, self._f),)
        return args

    def compile(self) -> float:
        """AOT lower + compile (the serve engine's warm-up); idempotent.
        Returns the compile wall seconds (0.0 on a warm hit)."""
        if self._exec is not None:
            return 0.0
        t0 = time.perf_counter()
        self._exec = self._runner.lower(*self._example_args()).compile()
        self.compile_seconds = time.perf_counter() - t0
        return self.compile_seconds

    def executable_payload(self):
        """The serialized compiled executable - (payload_bytes,
        in_tree, out_tree) for the persistent program cache
        (serve/progcache.py) - or None when not yet compiled.  Raises
        where the jaxlib cannot serialize; callers probe
        `progcache.aot_capability()` first."""
        if self._exec is None:
            return None
        from wavetpu.serve import progcache

        return progcache.serialize_executable(self._exec)

    def adopt_executable(self, payload) -> float:
        """Install a deserialized executable (the disk tier's warm
        path - skips lower+compile entirely); returns the deserialize
        wall seconds.  Raises on an incompatible payload - the caller
        counts it a cache miss and compiles fresh."""
        from wavetpu.serve import progcache

        t0 = time.perf_counter()
        self._exec = progcache.load_executable(payload)
        self.compile_seconds = time.perf_counter() - t0
        return self.compile_seconds

    def run(self, lanes: Sequence[LaneSpec]):
        """Execute the batch; returns (outputs, init_seconds,
        solve_seconds) with outputs = (u_prev_b, u_cur_b, abs_b, rel_b).
        init_seconds is the compile time this call paid (0 when warm)."""
        return run_batch(self, lanes)


def run_batch(solver, lanes: Sequence[LaneSpec]):
    """`solver.run` of the vmapped and the sharded batched solvers: the
    host's lane packing (span `ensemble.pack`), then the execute through
    `block_until_ready` and the error-block readback (`ensemble.run`,
    timed as solve_seconds; attr `masked`: `solver.masked(lanes)`)."""
    import jax

    init_s = solver.compile()
    with tracing.span("ensemble.pack"):
        args = solver.pack(lanes)
    with tracing.span("ensemble.run", masked=solver.masked(lanes)):
        t0 = time.perf_counter()
        out = solver._exec(*args)
        jax.block_until_ready(out)
        # Readback proves execution (the same reasoning as
        # leapfrog._timed_compile_run's sync): the (B, T+1) error block
        # is the smallest always-present output.
        np.asarray(out[2])
        solve_s = time.perf_counter() - t0
    return out, init_s, solve_s


@functools.lru_cache(maxsize=None)
def _lane_splitter():
    """One jitted dispatch that splits batched states into their lanes
    (one program per batch shape, compiled with the bucket's first
    batch)."""
    import jax

    return jax.jit(lambda *xs: tuple(
        [x[i] for i in range(x.shape[0])] for x in xs
    ))


def _lane_results(problem, outputs, lanes, init_s, solve_s):
    """Per-lane SolveResults from batched outputs (padding already
    dropped by the caller passing only real lanes and their indices),
    in the span `ensemble.results`.  The error blocks come to the host
    once each (`run_batch` already read the first), and the states split
    in one dispatch: per-lane slicing cost a dispatch, and a readback
    for each error row, per lane."""
    from wavetpu.solver.leapfrog import SolveResult

    upb, ucb, ab, rb = outputs
    results = []
    with tracing.span("ensemble.results"):
        ab = np.asarray(ab, np.float64)
        rb = np.asarray(rb, np.float64)
        ups, ucs = _lane_splitter()(upb, ucb)
        for i, lane in enumerate(lanes):
            s = lane.stop(problem)
            results.append(
                SolveResult(
                    problem=problem,
                    u_prev=ups[i],
                    u_cur=ucs[i],
                    abs_errors=ab[i, : s + 1],
                    rel_errors=rb[i, : s + 1],
                    init_seconds=init_s,
                    solve_seconds=solve_s,
                    steps_computed=s,
                    final_step=s,
                )
            )
    return results


def solve_ensemble(
    problem: Problem,
    lanes: Sequence[LaneSpec],
    dtype=None,
    scheme: str = "standard",
    path: str = "roll",
    k: int = 4,
    compute_errors: bool = True,
    interpret: Optional[bool] = None,
    block_x: Optional[int] = None,
    pad_to: Optional[int] = None,
    solver: Optional[EnsembleSolver] = None,
) -> EnsembleResult:
    """Solve a batch of lanes as one vmapped program.

    `pad_to` rounds the batch up to a program-cache bucket with
    `padding_lane()`s that stop where the batch's longest real lane
    does (dropped from `results`).  Pass a pre-built
    `solver` (the serve engine's cached program) to skip rebuilding; its
    geometry must match.
    """
    import jax.numpy as jnp

    dtype = jnp.float32 if dtype is None else dtype
    lanes = list(lanes)
    with_field = _validate(problem, lanes, path, k, compute_errors,
                           scheme)
    if with_field:
        lanes = fill_fields(problem, lanes)
    batch = lanes
    if pad_to is not None:
        if pad_to < len(lanes):
            raise ValueError(
                f"pad_to={pad_to} < {len(lanes)} real lanes"
            )
        stop = max(lane.stop(problem) for lane in lanes)
        pad = [padding_lane(stop)] * (pad_to - len(lanes))
        batch = lanes + (fill_fields(problem, pad) if with_field else pad)
    if solver is None:
        solver = EnsembleSolver(
            problem, len(batch), dtype=dtype, path=path, k=k,
            compute_errors=compute_errors, interpret=interpret,
            block_x=block_x, with_field=with_field, scheme=scheme,
        )
    outputs, init_s, solve_s = solver.run(batch)
    return EnsembleResult(
        problem=problem,
        results=_lane_results(problem, outputs, lanes, init_s, solve_s),
        path=path,
        batch_size=len(batch),
        n_lanes=len(lanes),
        init_seconds=init_s,
        solve_seconds=solve_s,
        u_prev_batch=outputs[0],
        u_cur_batch=outputs[1],
        masked=solver.masked(batch),
    )
