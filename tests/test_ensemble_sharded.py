"""Sharded x batched contracts (wavetpu/ensemble/sharded.py).

The load-bearing invariant mirrors tests/test_ensemble.py's at mesh
scale: every lane of a batched SHARDED solve - the shard_map-of-vmap
composition of the ensemble axis with the device mesh - is BITWISE
identical to the same problem solved solo through
`sharded.solve_sharded` on the same mesh, including per-lane phases,
per-lane stop layers, and padded batches.  Runs on the suite's 8
virtual CPU devices; the headline mesh is (2, 2, 1).
"""

import numpy as np
import pytest

from wavetpu.core.problem import Problem
from wavetpu.ensemble import batched as eb
from wavetpu.ensemble import sharded as es
from wavetpu.solver import sharded


def _bitwise(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


MESH = (2, 2, 1)


@pytest.fixture(scope="module")
def problem():
    return Problem(N=16, timesteps=9)


@pytest.fixture(scope="module")
def lanes():
    # default phase, shifted phase, shifted phase + early stop
    return [
        eb.LaneSpec(),
        eb.LaneSpec(phase=1.0),
        eb.LaneSpec(phase=0.5, stop_step=5),
    ]


def _assert_lane_parity(res, solos):
    for got, solo in zip(res.results, solos):
        assert _bitwise(got.u_cur, solo.u_cur)
        assert _bitwise(got.u_prev, solo.u_prev)
        assert got.final_step == solo.final_step
        assert np.array_equal(got.abs_errors, solo.abs_errors)
        assert np.array_equal(got.rel_errors, solo.rel_errors)


class TestShardedLaneParity:
    def test_roll_on_221_mesh(self, problem, lanes):
        res = es.solve_ensemble_sharded(
            problem, lanes, mesh_shape=MESH, kernel="roll"
        )
        solos = [
            sharded.solve_sharded(
                problem, mesh_shape=MESH, kernel="roll",
                phase=lane.phase, stop_step=lane.stop(problem),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_pallas_on_221_mesh(self, problem, lanes):
        res = es.solve_ensemble_sharded(
            problem, lanes, mesh_shape=MESH, kernel="pallas",
            interpret=True,
        )
        solos = [
            sharded.solve_sharded(
                problem, mesh_shape=MESH, kernel="pallas",
                interpret=True, phase=lane.phase,
                stop_step=lane.stop(problem),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_x_only_mesh(self, problem, lanes):
        res = es.solve_ensemble_sharded(
            problem, lanes, mesh_shape=(4, 1, 1), kernel="roll"
        )
        solos = [
            sharded.solve_sharded(
                problem, mesh_shape=(4, 1, 1), kernel="roll",
                phase=lane.phase, stop_step=lane.stop(problem),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_padded_batch_leaves_real_lanes_bitwise_unchanged(
        self, problem, lanes
    ):
        plain = es.solve_ensemble_sharded(
            problem, lanes, mesh_shape=MESH, kernel="roll"
        )
        padded = es.solve_ensemble_sharded(
            problem, lanes, mesh_shape=MESH, kernel="roll", pad_to=4
        )
        assert padded.batch_size == 4 and padded.n_lanes == 3
        for a, b in zip(padded.results, plain.results):
            assert _bitwise(a.u_cur, b.u_cur)
            assert _bitwise(a.u_prev, b.u_prev)
            assert np.array_equal(a.abs_errors, b.abs_errors)


class TestSoloShardedPhase:
    def test_default_phase_is_the_reference_program(self, problem):
        a = sharded.solve_sharded(problem, mesh_shape=MESH, kernel="roll")
        b = sharded.solve_sharded(
            problem, mesh_shape=MESH, kernel="roll", phase=2.0 * np.pi
        )
        assert _bitwise(a.u_cur, b.u_cur)
        assert np.array_equal(a.abs_errors, b.abs_errors)

    def test_shifted_phase_errors_stay_discretization_small(self):
        p = Problem(N=16, timesteps=9)
        ref = sharded.solve_sharded(
            p, mesh_shape=MESH, kernel="roll"
        ).abs_errors.max()
        e = sharded.solve_sharded(
            p, mesh_shape=MESH, kernel="roll", phase=1.0
        ).abs_errors.max()
        # without the analytic layer-1 bootstrap this is O(1)
        assert e < 10 * ref, f"{e} vs ref {ref}"

    def test_sharded_phase_matches_single_device(self, problem):
        # The (1,1,1) sharded roll program and the single-device roll
        # solver integrate the same shifted-phase IVP to the same class.
        s = sharded.solve_sharded(
            problem, mesh_shape=(1, 1, 1), kernel="roll", phase=1.0
        )
        from wavetpu.solver import leapfrog

        solo = leapfrog.solve(problem, phase=1.0)
        assert s.abs_errors.max() == pytest.approx(
            solo.abs_errors.max(), rel=1e-3
        )

    def test_compensated_rejects_shifted_phase(self, problem):
        with pytest.raises(ValueError, match="reference phase"):
            sharded.solve_sharded(
                problem, mesh_shape=MESH, kernel="roll",
                scheme="compensated", phase=1.0,
            )


class TestShardedValidation:
    def test_field_lanes_rejected(self, problem):
        field = np.full((problem.N,) * 3, problem.a2tau2)
        with pytest.raises(ValueError, match="field"):
            es.solve_ensemble_sharded(
                problem, [eb.LaneSpec(c2tau2_field=field)],
                mesh_shape=MESH, compute_errors=False,
            )

    def test_empty_batch_rejected(self, problem):
        with pytest.raises(ValueError, match="at least one lane"):
            es.solve_ensemble_sharded(problem, [], mesh_shape=MESH)

    def test_bad_kernel_rejected(self, problem):
        with pytest.raises(ValueError, match="kernel"):
            es.solve_ensemble_sharded(
                problem, [eb.LaneSpec()], mesh_shape=MESH, kernel="cuda"
            )

    def test_stop_out_of_range(self, problem):
        with pytest.raises(ValueError, match="stop_step"):
            es.solve_ensemble_sharded(
                problem, [eb.LaneSpec(stop_step=99)], mesh_shape=MESH
            )
