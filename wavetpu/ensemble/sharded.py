"""Sharded x batched: an ensemble axis composed with the device mesh.

PR 3's vmapped core batches SINGLE-DEVICE solves; this module composes
the lane axis with the (MX, MY, MZ) mesh axes so a multi-chip host can
serve a batch of SHARDED solves as one program - the pod-scale
throughput composition of arXiv:2108.11076 (batch axis x device mesh).

Mechanism: shard_map-of-vmap.  The state rides as (B,) + topo.padded
sharded P(None, "x", "y", "z") - lane-major over the batch axis, spatial
axes on the mesh exactly as solver/sharded.py lays them out - and inside
shard_map the per-lane local march (the SAME op sequence
`sharded._local_solve_fns` runs: halo ppermutes, boundary masking,
pmax'd error reductions) is vmapped over the lane axis.  Collectives
batch under vmap (ppermute/pmax have batching rules), so every lane's
per-shard ops mirror the solo sharded solve op for op - the BITWISE
lane-parity contract of tests/test_ensemble_sharded.py, the sharded twin
of ensemble/batched.py's.

Lane identity is (phase, stop_step) - per-lane runtime (B, T+1) ct
tables, the per-lane taylor/analytic bootstrap selector, and per-layer
`where` stop masking (no k-block constraint: the sharded lane marches
the 1-step kernel).  Per-lane c2tau2 fields are not wired (constant
speed only); scheme is "standard" (the distributed velocity-form
flagship still serves solo via solver/kfused_comp.py).
"""

from __future__ import annotations

import time
from typing import Optional, Sequence, Tuple

import numpy as np

from wavetpu.core.problem import Problem
from wavetpu.ensemble.batched import (
    EnsembleResult,
    LaneSpec,
    _lane_results,
    padding_lane,
    run_batch,
)
from wavetpu.verify import oracle

KERNELS = ("roll", "pallas")


def _validate(problem: Problem, lanes: Sequence[LaneSpec], kernel: str,
              compute_errors: bool) -> None:
    if kernel not in KERNELS:
        raise ValueError(
            f"kernel must be one of {KERNELS}, got {kernel!r}"
        )
    if not lanes:
        raise ValueError("an ensemble needs at least one lane")
    for i, lane in enumerate(lanes):
        if lane.c2tau2_field is not None:
            raise ValueError(
                f"lane {i}: per-lane c2tau2 fields are not wired through "
                f"the sharded ensemble (constant speed only)"
            )
        s = lane.stop(problem)
        if not 1 <= s <= problem.timesteps:
            raise ValueError(
                f"lane {i}: stop_step must be in [1, {problem.timesteps}],"
                f" got {s}"
            )


class ShardedEnsembleSolver:
    """One compiled shard_map-of-vmap program for (problem, mesh, batch).

    The sharded twin of `batched.EnsembleSolver` - same
    compile()/pack()/run() contract, so the serve engine's program cache
    holds either interchangeably.  Lane programs mirror
    `sharded.make_sharded_solver`'s local op sequence (kernel="roll" or
    "pallas", serial exchange, standard scheme).
    """

    def __init__(
        self,
        problem: Problem,
        n_lanes: int,
        mesh_shape: Tuple[int, int, int],
        dtype=None,
        kernel: str = "roll",
        compute_errors: bool = True,
        interpret: Optional[bool] = None,
        devices=None,
    ):
        import jax
        import jax.numpy as jnp
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from wavetpu.core.grid import AXIS_NAMES
        from wavetpu.kernels import stencil_ref
        from wavetpu.solver import sharded

        if n_lanes < 1:
            raise ValueError(f"n_lanes must be >= 1, got {n_lanes}")
        if kernel not in KERNELS:
            raise ValueError(
                f"kernel must be one of {KERNELS}, got {kernel!r}"
            )
        if interpret is None:
            interpret = jax.default_backend() != "tpu"
        self.problem = problem
        self.n_lanes = n_lanes
        self.mesh_shape = tuple(int(m) for m in mesh_shape)
        self.dtype = jnp.float32 if dtype is None else dtype
        self.kernel = kernel
        self.compute_errors = compute_errors
        self._f = stencil_ref.compute_dtype(self.dtype)
        self._exec = None
        self.compile_seconds: Optional[float] = None
        topo, mesh = sharded._resolve_mesh(
            problem, self.mesh_shape, devices
        )
        self.topo = topo
        f = self._f
        dtype_s = self.dtype
        nsteps = problem.timesteps
        (sx, sy, sz), bcs, mes, _ct = sharded._replicated_inputs(
            problem, topo, dtype_s
        )
        step = sharded._make_local_step(
            problem, topo, dtype_s, kernel, False, interpret
        )
        compute = compute_errors

        def lane_body(ct, stop, taylor, sx, sy, sz, bcx, bcy, bcz,
                      mex, mey, mez):
            # Per-lane local march: the op sequence of
            # sharded._local_solve_fns (errors_fn/bootstrap/scan_layers)
            # with the ct table a runtime argument, both bootstrap
            # branches computed and `where`-selected per lane, and
            # per-layer stop masking.
            def errors(u, layer):
                if not compute:
                    z = jnp.zeros((), f)
                    return z, z
                field = oracle.analytic_field(sx, sy, sz, ct[layer])
                ae, re = oracle.layer_errors(
                    u.astype(f), field, mex, mey, mez
                )
                return (
                    lax.pmax(ae, AXIS_NAMES),
                    lax.pmax(re, AXIS_NAMES),
                )

            bc = (
                bcx[:, None, None] * bcy[None, :, None]
                * bcz[None, None, :]
            )
            u0 = (
                oracle.analytic_field(sx, sy, sz, ct[0]) * bc
            ).astype(dtype_s)
            s = step(u0, u0, bc, None)
            u1_step = (0.5 * (u0.astype(f) + s.astype(f))).astype(dtype_s)
            u1_an = (
                oracle.analytic_field(sx, sy, sz, ct[1]) * bc
            ).astype(dtype_s)
            u1 = jnp.where(taylor, u1_step, u1_an)
            a0 = r0 = jnp.zeros((), f)
            a1, r1 = errors(u1, 1)

            def body(carry, n):
                u_prev, u = carry
                u_next = step(u_prev, u, bc, None)
                live = n <= stop
                ae, re = errors(u_next, n)
                ae = jnp.where(live, ae, jnp.zeros((), f))
                re = jnp.where(live, re, jnp.zeros((), f))
                return (
                    jnp.where(live, u, u_prev),
                    jnp.where(live, u_next, u),
                ), (ae, re)

            (u_prev, u_cur), (abs_t, rel_t) = lax.scan(
                body, (u0, u1), jnp.arange(2, nsteps + 1)
            )
            return (
                u_prev,
                u_cur,
                jnp.concatenate([jnp.stack([a0, a1]), abs_t]),
                jnp.concatenate([jnp.stack([r0, r1]), rel_t]),
            )

        def local_batch(cts, stops, taylors, sx, sy, sz, bcx, bcy, bcz,
                        mex, mey, mez):
            return jax.vmap(
                lane_body, in_axes=(0, 0, 0) + (None,) * 9
            )(cts, stops, taylors, sx, sy, sz, bcx, bcy, bcz,
              mex, mey, mez)

        state_spec = P(None, *AXIS_NAMES)
        sharded_fn = jax.shard_map(
            local_batch,
            mesh=mesh,
            in_specs=(
                P(), P(), P(),
                P("x"), P("y"), P("z"),
                P("x"), P("y"), P("z"),
                P("x"), P("y"), P("z"),
            ),
            out_specs=(state_spec, state_spec, P(), P()),
            check_vma=False,
        )

        def run(cts, stops, taylors):
            return sharded_fn(cts, stops, taylors, sx, sy, sz, *bcs, *mes)

        self._runner = jax.jit(run)

    # ---- packing / compiling / running (EnsembleSolver contract) ----

    def pack(self, lanes: Sequence[LaneSpec]) -> Tuple:
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
        taylor = np.asarray(
            [lane.phase == oracle.TWO_PI for lane in lanes], bool
        )
        return (
            jnp.asarray(cts, self._f),
            jnp.asarray(stops),
            jnp.asarray(taylor),
        )

    def _example_args(self) -> Tuple:
        import jax.numpy as jnp

        b, t = self.n_lanes, self.problem.timesteps
        return (
            jnp.zeros((b, t + 1), self._f),
            jnp.ones((b,), jnp.int32),
            jnp.ones((b,), bool),
        )

    def compile(self) -> float:
        if self._exec is not None:
            return 0.0
        t0 = time.perf_counter()
        self._exec = self._runner.lower(*self._example_args()).compile()
        self.compile_seconds = time.perf_counter() - t0
        return self.compile_seconds

    def executable_payload(self):
        """Serialized executable for the persistent program cache -
        same contract as `batched.EnsembleSolver.executable_payload`
        (the two program types share the disk tier)."""
        if self._exec is None:
            return None
        from wavetpu.serve import progcache

        return progcache.serialize_executable(self._exec)

    def adopt_executable(self, payload) -> float:
        """Install a deserialized executable; see
        `batched.EnsembleSolver.adopt_executable`."""
        from wavetpu.serve import progcache

        t0 = time.perf_counter()
        self._exec = progcache.load_executable(payload)
        self.compile_seconds = time.perf_counter() - t0
        return self.compile_seconds

    def masked(self, lanes: Sequence[LaneSpec]) -> bool:
        """The sharded lane program masks every step, whatever the
        stops (`batched.EnsembleSolver.masked`'s contract)."""
        return True

    def run(self, lanes: Sequence[LaneSpec]):
        return run_batch(self, lanes)


def solve_ensemble_sharded(
    problem: Problem,
    lanes: Sequence[LaneSpec],
    mesh_shape: Tuple[int, int, int],
    dtype=None,
    kernel: str = "roll",
    compute_errors: bool = True,
    interpret: Optional[bool] = None,
    devices=None,
    pad_to: Optional[int] = None,
    solver: Optional[ShardedEnsembleSolver] = None,
) -> EnsembleResult:
    """Solve a batch of lanes as ONE sharded batched program over
    `mesh_shape`.  Same padding / pre-built-solver contract as
    `batched.solve_ensemble`; every lane is bitwise equal to its solo
    `sharded.solve_sharded` on the same mesh (u_prev/u_cur are the
    PADDED topo arrays, as the solo solver returns them)."""
    import jax.numpy as jnp

    dtype = jnp.float32 if dtype is None else dtype
    lanes = list(lanes)
    _validate(problem, lanes, kernel, compute_errors)
    batch = lanes
    if pad_to is not None:
        if pad_to < len(lanes):
            raise ValueError(f"pad_to={pad_to} < {len(lanes)} real lanes")
        batch = lanes + [padding_lane()] * (pad_to - len(lanes))
    if solver is None:
        solver = ShardedEnsembleSolver(
            problem, len(batch), mesh_shape, dtype=dtype, kernel=kernel,
            compute_errors=compute_errors, interpret=interpret,
            devices=devices,
        )
    outputs, init_s, solve_s = solver.run(batch)
    return EnsembleResult(
        problem=problem,
        results=_lane_results(problem, outputs, lanes, init_s, solve_s),
        path=f"sharded{tuple(mesh_shape)}:{kernel}",
        batch_size=len(batch),
        n_lanes=len(lanes),
        init_seconds=init_s,
        solve_seconds=solve_s,
        u_prev_batch=outputs[0],
        u_cur_batch=outputs[1],
        masked=True,
    )
