"""Pallas kernel parity vs the jnp-roll semantic reference.

The fused kernel (kernels/stencil_pallas.py) must agree with
`stencil_ref.leapfrog_step` / `taylor_half_step` to rounding error on
identical inputs (SURVEY.md section 4(e)).  Runs in interpret mode on the
CPU test backend; on-chip throughput is the benchmark's job
(BENCHMARK.json).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from wavetpu.core.problem import Problem
from wavetpu.kernels import stencil_pallas, stencil_ref
from wavetpu.solver import leapfrog


def _random_state(n, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    u_prev = jnp.asarray(rng.standard_normal((n, n, n)), dtype)
    u = jnp.asarray(rng.standard_normal((n, n, n)), dtype)
    # Establish the Dirichlet invariant the solver maintains.
    return stencil_ref.apply_dirichlet(u_prev), stencil_ref.apply_dirichlet(u)


@pytest.mark.parametrize("block_x", [1, 2, 4])
def test_leapfrog_step_matches_ref(small_problem, block_x):
    """Interior + periodic wrap + Dirichlet all agree for every slab depth
    (block_x=1 exercises the pure halo-plane path, >1 the slab interior)."""
    u_prev, u = _random_state(small_problem.N)
    want = stencil_ref.leapfrog_step(u_prev, u, small_problem)
    got = stencil_pallas.leapfrog_step(
        u_prev, u, small_problem, block_x=block_x, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-6, rtol=1e-6
    )


def test_taylor_half_step_matches_ref(small_problem):
    u0, _ = _random_state(small_problem.N, seed=1)
    want = stencil_ref.taylor_half_step(u0, small_problem)
    got = stencil_pallas.taylor_half_step(
        u0, small_problem, block_x=2, interpret=True
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-6, rtol=1e-6
    )


def test_full_solve_with_pallas_step(small_problem):
    """End-to-end: the solver with the Pallas step reproduces the reference
    solver's fields and per-layer error trajectory."""
    ref = leapfrog.solve(small_problem)
    pal = leapfrog.solve(
        small_problem,
        step_fn=stencil_pallas.make_step_fn(block_x=2, interpret=True),
    )
    np.testing.assert_allclose(
        np.asarray(pal.u_cur), np.asarray(ref.u_cur), atol=1e-5, rtol=0.0
    )
    np.testing.assert_allclose(
        pal.abs_errors, ref.abs_errors, atol=1e-6, rtol=1e-4
    )


def test_dirichlet_planes_zeroed(small_problem):
    u_prev, u = _random_state(small_problem.N, seed=2)
    got = np.asarray(
        stencil_pallas.leapfrog_step(
            u_prev, u, small_problem, block_x=1, interpret=True
        )
    )
    assert np.all(got[:, 0, :] == 0.0)
    assert np.all(got[:, :, 0] == 0.0)


def test_choose_block_x():
    """Slab depth divides N and respects the VMEM working-set budget."""
    for n in (16, 128, 256, 512, 1024):
        bx = stencil_pallas.choose_block_x(n)
        assert n % bx == 0
        # The budget bounds any slab deeper than the bx=1 floor.
        assert (
            bx == 1
            or 2 * (3 * bx + 2) * n * n * 4 <= stencil_pallas._VMEM_BUDGET
        )
    assert stencil_pallas.choose_block_x(512) == 8
    assert stencil_pallas.choose_block_x(1024) == 1
    assert stencil_pallas.choose_block_x(128) == 8
    # The variable-c kernel has one more bx-deep slab in flight, so the
    # budget admits a shallower slab (measured cliff on v5e, see docstring).
    assert stencil_pallas.choose_block_x(512, field_itemsize=4) == 4
    # bf16 state still carries an f32 field slab - it must be counted at
    # the compute width, not the state width.
    assert (
        stencil_pallas.choose_block_x(512, itemsize=2, field_itemsize=4) == 8
    )
    full = 2 * ((3 * 2 + 4) * 8 + 2 * 2) * 512 * 512
    assert full <= stencil_pallas._VMEM_BUDGET
