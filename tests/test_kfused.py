"""k-fused temporal-blocking solver: parity, errors, tails, resume.

The k-fused path (solver/kfused.py driving stencil_pallas.fused_kstep)
must be bitwise identical to the 1-step pallas solve - same per-substep
ops - and its in-kernel per-layer error factorization must reproduce the
post-hoc oracle (verify/oracle.py) for every layer, including the
intermediate layers that never reach HBM.  Interpret mode on the CPU
backend (tests/conftest.py); on-chip throughput is the benchmark's job.
"""

import functools

import jax.numpy as jnp
import numpy as np
import pytest

from wavetpu.core.problem import Problem
from wavetpu.kernels import stencil_pallas
from wavetpu.solver import kfused, leapfrog


@functools.lru_cache(maxsize=None)
def _pallas_solve(problem, dtype=jnp.float32, **kw):
    """Memoized 1-step pallas reference solve (Problem is frozen, hence a
    valid cache key): the parity matrix reuses the same configs, each
    paying an interpret-mode compile."""
    return leapfrog.solve(
        problem, dtype=dtype,
        step_fn=stencil_pallas.make_step_fn(interpret=True), **kw
    )


@pytest.mark.parametrize("k,timesteps", [(2, 11), (4, 9), (4, 13), (8, 9)])
def test_state_bitwise_vs_1step_pallas(k, timesteps):
    """k-fused layers are op-identical to 1-step pallas layers - the final
    state must match BITWISE (this is what makes stop/resume mixing of the
    two paths safe), for block counts with and without a remainder tail."""
    p = Problem(N=16, timesteps=timesteps)
    want = _pallas_solve(p)
    got = kfused.solve_kfused(p, k=k, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got.u_cur), np.asarray(want.u_cur)
    )
    np.testing.assert_array_equal(
        np.asarray(got.u_prev), np.asarray(want.u_prev)
    )


@pytest.mark.parametrize("k", [2, 4])
def test_per_layer_errors_match_oracle(k):
    """Every layer's abs/rel error - including in-VMEM intermediate layers -
    agrees with the separate post-hoc oracle pass of the 1-step path."""
    p = Problem(N=16, timesteps=11)
    want = _pallas_solve(p)
    got = kfused.solve_kfused(p, k=k, interpret=True)
    np.testing.assert_allclose(
        got.abs_errors, want.abs_errors, rtol=1e-5, atol=1e-7
    )
    # rel errors include near-singular analytic planes (sx ~ 1e-16) where
    # the value is huge and meaningless but must still agree relatively.
    np.testing.assert_allclose(
        got.rel_errors, want.rel_errors, rtol=1e-5
    )


def test_against_jnp_roll_reference():
    """End-to-end agreement with the semantic jnp reference to rounding."""
    p = Problem(N=16, timesteps=10)
    want = leapfrog.solve(p)
    got = kfused.solve_kfused(p, k=2, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got.u_cur), np.asarray(want.u_cur), atol=1e-6
    )
    np.testing.assert_allclose(
        got.abs_errors, want.abs_errors, rtol=1e-4, atol=1e-7
    )


@pytest.mark.heavy
def test_stop_resume_bitwise_across_paths():
    """stop at an arbitrary layer (not a k boundary), resume k-fused OR
    1-step: all three final states bitwise equal the uninterrupted run."""
    p = Problem(N=16, timesteps=13)
    full = kfused.solve_kfused(p, k=4, interpret=True)
    part = kfused.solve_kfused(p, k=4, stop_step=6, interpret=True)
    assert part.final_step == 6
    resumed_k = kfused.resume_kfused(
        p, part.u_prev, part.u_cur, start_step=6, k=4, interpret=True
    )
    resumed_1 = leapfrog.resume(
        p, part.u_prev, part.u_cur, start_step=6,
        step_fn=stencil_pallas.make_step_fn(interpret=True),
    )
    np.testing.assert_array_equal(
        np.asarray(resumed_k.u_cur), np.asarray(full.u_cur)
    )
    np.testing.assert_array_equal(
        np.asarray(resumed_1.u_cur), np.asarray(full.u_cur)
    )
    # error arrays: head zeros, tail matches the full run's tail
    np.testing.assert_allclose(
        resumed_k.abs_errors[7:], full.abs_errors[7:], rtol=1e-6
    )
    assert (resumed_k.abs_errors[:7] == 0).all()


@pytest.mark.parametrize("timesteps,stop", [(21, 9), (17, 9)])
def test_block_aligned_split_bitwise(timesteps, stop):
    """A solve stopped on the block grid and resumed k-fused reproduces
    the uninterrupted march's fields AND error rows bitwise, for odd (5)
    and even (4) block counts in the uninterrupted march.  (Each part
    keeps two blocks or more: on XLA's CPU backend a one-block march's
    rel rows can land 1 ulp from the same block's in a longer march.)"""
    p = Problem(N=16, timesteps=timesteps)
    full = kfused.solve_kfused(p, k=4, interpret=True)
    part = kfused.solve_kfused(p, k=4, stop_step=stop, interpret=True)
    rest = kfused.resume_kfused(
        p, part.u_prev, part.u_cur, start_step=stop, k=4, interpret=True
    )
    for name in ("u_prev", "u_cur"):
        np.testing.assert_array_equal(
            np.asarray(getattr(rest, name)), np.asarray(getattr(full, name))
        )
    for name in ("abs_errors", "rel_errors"):
        want = getattr(full, name)
        np.testing.assert_array_equal(getattr(part, name), want[:stop + 1])
        np.testing.assert_array_equal(
            getattr(rest, name)[stop + 1:], want[stop + 1:]
        )


def test_bf16_state_bitwise_vs_1step():
    """Per-substep quantization keeps bf16 k-fused bitwise equal to bf16
    1-step pallas, and the observed errors match its error pass."""
    p = Problem(N=16, timesteps=9)
    want = _pallas_solve(p, dtype=jnp.bfloat16)
    got = kfused.solve_kfused(p, dtype=jnp.bfloat16, k=4, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got.u_cur.astype(jnp.float32)),
        np.asarray(want.u_cur.astype(jnp.float32)),
    )
    np.testing.assert_allclose(
        got.abs_errors, want.abs_errors, rtol=1e-5, atol=1e-7
    )


def test_no_errors_mode():
    p = Problem(N=16, timesteps=9)
    got = kfused.solve_kfused(p, k=4, compute_errors=False, interpret=True)
    assert (got.abs_errors == 0).all() and (got.rel_errors == 0).all()
    want = _pallas_solve(p, compute_errors=False)
    np.testing.assert_array_equal(
        np.asarray(got.u_cur), np.asarray(want.u_cur)
    )


def test_validation_errors():
    p = Problem(N=16, timesteps=9)
    with pytest.raises(ValueError, match="k must be >= 2"):
        kfused.solve_kfused(p, k=1, interpret=True)
    with pytest.raises(ValueError, match="must divide N"):
        kfused.solve_kfused(Problem(N=18, timesteps=9), k=4, interpret=True)
    with pytest.raises(ValueError, match="stop_step"):
        kfused.solve_kfused(p, k=2, stop_step=99, interpret=True)


def test_choose_kstep_block():
    """bx respects divisibility (n % bx, k | bx) and the VMEM model."""
    assert stencil_pallas.choose_kstep_block(512, 2) == 8
    assert stencil_pallas.choose_kstep_block(512, 4) == 4
    assert stencil_pallas.choose_kstep_block(16, 4) == 8
    # bf16 state halves the pipeline slabs: k=4 fits at bx=8
    assert stencil_pallas.choose_kstep_block(512, 4, itemsize=2) == 8
    # absurd k at large N: nothing fits
    assert stencil_pallas.choose_kstep_block(4096, 8) is None
