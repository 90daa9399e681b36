"""Ensemble-core contracts (wavetpu/ensemble/batched.py).

The load-bearing invariant: every lane of a batched solve is BITWISE
identical to the same problem solved solo on the same path - including
per-lane phases, per-lane stop layers (frozen by masking), per-lane
c2tau2 fields, and padded batches, masked or not.  A change to
either the ensemble lane programs or the solo solvers that breaks these
equalities is a correctness regression, not a tolerance issue.
"""

import dataclasses

import numpy as np
import pytest

from wavetpu.core.problem import Problem
from wavetpu.ensemble import batched as eb
from wavetpu.kernels import stencil_pallas, stencil_ref
from wavetpu.solver import kfused, kfused_comp, leapfrog


def _bitwise(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


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


class TestLaneParity:
    def test_roll(self, problem, lanes):
        res = eb.solve_ensemble(problem, lanes, path="roll")
        solos = [
            leapfrog.solve(
                problem, phase=lane.phase, stop_step=lane.stop(problem)
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_pallas(self, problem, lanes):
        res = eb.solve_ensemble(
            problem, lanes, path="pallas", interpret=True
        )
        solos = [
            leapfrog.solve(
                problem,
                step_fn=stencil_pallas.make_step_fn(interpret=True),
                phase=lane.phase,
                stop_step=lane.stop(problem),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_kfused(self, problem, lanes):
        res = eb.solve_ensemble(
            problem, lanes, path="kfused", k=2, interpret=True
        )
        solos = [
            kfused.solve_kfused(
                problem, k=2, interpret=True, phase=lane.phase,
                stop_step=lane.stop(problem),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_kfused_remainder_tail(self, lanes):
        # (10 - 1) % 2 == 1: the batch runs the masked 1-step tail the
        # solo march also runs.
        p10 = Problem(N=16, timesteps=10)
        res = eb.solve_ensemble(
            p10, lanes, path="kfused", k=2, interpret=True
        )
        solos = [
            kfused.solve_kfused(
                p10, k=2, interpret=True, phase=lane.phase,
                stop_step=lane.stop(p10),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)


class TestPadding:
    def test_padded_lanes_leave_real_lanes_bitwise_unchanged(
        self, problem, lanes
    ):
        plain = eb.solve_ensemble(problem, lanes, path="roll")
        padded = eb.solve_ensemble(problem, lanes, path="roll", pad_to=8)
        assert padded.batch_size == 8
        assert padded.n_lanes == 3
        assert len(padded.results) == 3
        for a, b in zip(padded.results, plain.results):
            assert _bitwise(a.u_cur, b.u_cur)
            assert _bitwise(a.u_prev, b.u_prev)
            assert np.array_equal(a.abs_errors, b.abs_errors)

    def test_padding_lane_freezes_on_every_k_grid(self):
        lane = eb.padding_lane()
        assert lane.stop_step == 1  # (1-1) % k == 0 for all k

    def test_pad_below_batch_rejected(self, problem, lanes):
        with pytest.raises(ValueError, match="pad_to"):
            eb.solve_ensemble(problem, lanes, path="roll", pad_to=2)


# Lanes of each case, padded to 4: (a) every lane runs to the last layer,
# the unmasked march; (b) a real early stop beside a full lane, and (c)
# one real lane stopping at layer 1, both masked.
MARCH_CASES = {
    "full": ([eb.LaneSpec(), eb.LaneSpec(phase=1.0),
              eb.LaneSpec(phase=0.5)], False),
    "mixed": ([eb.LaneSpec(phase=1.0, stop_step=5),
               eb.LaneSpec(phase=0.5)], True),
    "stop1": ([eb.LaneSpec(phase=0.5, stop_step=1)], True),
}


class TestLaneMarch:
    """The standard 1-step lane program branches once a batch: unmasked
    when every lane (padding included) runs to the last layer, masked
    otherwise - each lane bitwise its solo solve either way."""

    @pytest.mark.parametrize("case", sorted(MARCH_CASES))
    @pytest.mark.parametrize("path", ["roll", "pallas"])
    def test_padded_batch_matches_solo(self, problem, path, case):
        from wavetpu.serve.engine import ServeEngine
        from wavetpu.serve.scheduler import (
            DynamicBatcher,
            ServeMetrics,
            SolveRequest,
        )

        lanes, masked = MARCH_CASES[case]
        res = eb.solve_ensemble(
            problem, lanes, path=path, interpret=True, pad_to=4
        )
        assert res.batch_size == 4
        assert res.masked is masked
        step = (stencil_pallas.make_step_fn(interpret=True)
                if path == "pallas" else None)
        solos = [
            leapfrog.solve(
                problem, step_fn=step, phase=lane.phase,
                stop_step=lane.stop(problem),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

        metrics = ServeMetrics()
        batcher = DynamicBatcher(
            ServeEngine(bucket_sizes=(4,), interpret=True),
            metrics=metrics, max_wait=30.0, max_batch=len(lanes),
        )
        try:
            futs = [
                batcher.submit(
                    SolveRequest(problem=problem, lane=lane, path=path)
                )
                for lane in lanes
            ]
            for fut, solo in zip(futs, solos):
                got, err, info = fut.result(120)
                assert err is None
                assert info["batch_size"] == 4
                assert _bitwise(got.u_cur, solo.u_cur)
        finally:
            batcher.close()
        snap = metrics.snapshot()
        assert snap["batches_total"] == 1
        assert snap["masked_batches_total"] == int(masked)
        assert snap["unmasked_batches_total"] == int(not masked)

    def test_padding_takes_the_longest_real_stop(self, problem):
        lanes = [eb.LaneSpec(stop_step=5), eb.LaneSpec(stop_step=3)]
        res = eb.solve_ensemble(problem, lanes, path="roll", pad_to=4)
        assert res.masked
        assert eb.padding_lane(5).stop_step == 5
        solver = eb.EnsembleSolver(problem, 4, path="roll")
        full = [eb.LaneSpec()] * 3 + [eb.padding_lane(problem.timesteps)]
        assert not solver.masked(full)
        assert solver.masked(full[:3] + [eb.padding_lane()])

    @pytest.mark.parametrize("path", ["kfused", "compensated"])
    def test_other_lane_programs_always_mask(self, problem, path):
        scheme = "compensated" if path == "compensated" else "standard"
        solver = eb.EnsembleSolver(
            problem, 2, path="kfused" if path == "kfused" else "roll",
            k=2, scheme=scheme,
        )
        assert solver.masked([eb.LaneSpec(), eb.LaneSpec(phase=1.0)])


class TestFields:
    @pytest.fixture(scope="class")
    def field(self, problem):
        return stencil_ref.make_c2tau2_field(
            problem,
            lambda x, y, z: problem.a2 * (
                1.0 - 0.3 * np.exp(
                    -((x - 0.5) ** 2 + (y - 0.5) ** 2 + (z - 0.5) ** 2)
                    / 0.1
                )
            ),
        )

    def test_roll_field_parity(self, problem, field):
        lanes = [
            eb.LaneSpec(c2tau2_field=field),
            eb.LaneSpec(stop_step=7),
        ]
        res = eb.solve_ensemble(
            problem, lanes, path="roll", compute_errors=False
        )
        solo0 = leapfrog.solve(
            problem, step_fn=stencil_ref.make_variable_c_step(field),
            compute_errors=False,
        )
        assert _bitwise(res.results[0].u_cur, solo0.u_cur)
        # The field-less lane rides the variable-c kernel with the
        # CONSTANT tau^2 a^2 field (fill_fields) - bitwise the solo
        # variable-c solve with that constant field.
        const = np.full((problem.N,) * 3, problem.a2tau2)
        solo1 = leapfrog.solve(
            problem, step_fn=stencil_ref.make_variable_c_step(const),
            compute_errors=False, stop_step=7,
        )
        assert _bitwise(res.results[1].u_cur, solo1.u_cur)

    def test_field_batch_rejects_shifted_phase(self, problem, field):
        with pytest.raises(ValueError, match="analytic layer-1"):
            eb.solve_ensemble(
                problem,
                [eb.LaneSpec(c2tau2_field=field), eb.LaneSpec(phase=0.7)],
                path="roll", compute_errors=False,
            )

    def test_pallas_field_parity(self, problem, field):
        res = eb.solve_ensemble(
            problem, [eb.LaneSpec(c2tau2_field=field), eb.LaneSpec()],
            path="pallas", compute_errors=False, interpret=True,
        )
        solo = leapfrog.solve(
            problem,
            step_fn=stencil_pallas.make_step_fn(
                interpret=True, c2tau2_field=field
            ),
            compute_errors=False,
        )
        assert _bitwise(res.results[0].u_cur, solo.u_cur)

    def test_kfused_field_parity(self, problem, field):
        res = eb.solve_ensemble(
            problem, [eb.LaneSpec(c2tau2_field=field), eb.LaneSpec()],
            path="kfused", k=2, compute_errors=False, interpret=True,
        )
        solo = kfused.solve_kfused(
            problem, k=2, interpret=True, compute_errors=False,
            c2tau2_field=field,
        )
        assert _bitwise(res.results[0].u_cur, solo.u_cur)

    def test_field_with_errors_rejected(self, problem, field):
        with pytest.raises(ValueError, match="no analytic oracle"):
            eb.solve_ensemble(
                problem, [eb.LaneSpec(c2tau2_field=field)], path="roll",
                compute_errors=True,
            )

    def test_field_shape_checked(self, problem):
        with pytest.raises(ValueError, match="shape"):
            eb.solve_ensemble(
                problem,
                [eb.LaneSpec(c2tau2_field=np.zeros((4, 4, 4)))],
                path="roll", compute_errors=False,
            )


class TestCompensatedLaneParity:
    """The tentpole contract: flagship compensated (Kahan) lanes batch
    through the vmapped core BITWISE equal to their solo compensated
    solves - state, error vectors, shifted phases, early stops, padded
    batches - on all three paths.  `solve_ensemble` must never report a
    compensated fallback on a backend where the path vmaps."""

    def test_roll(self, problem, lanes):
        res = eb.solve_ensemble(
            problem, lanes, scheme="compensated", path="roll"
        )
        solos = [
            leapfrog.solve_compensated(
                problem, phase=lane.phase, stop_step=lane.stop(problem)
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_pallas(self, problem, lanes):
        res = eb.solve_ensemble(
            problem, lanes, scheme="compensated", path="pallas",
            interpret=True,
        )
        solos = [
            leapfrog.solve_compensated(
                problem,
                comp_step_fn=stencil_pallas.make_compensated_step_fn(
                    interpret=True
                ),
                phase=lane.phase, stop_step=lane.stop(problem),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_kfused_velocity_onion(self, problem, lanes):
        res = eb.solve_ensemble(
            problem, lanes, scheme="compensated", path="kfused", k=2,
            interpret=True,
        )
        solos = [
            kfused_comp.solve_kfused_comp(
                problem, k=2, interpret=True, phase=lane.phase,
                stop_step=lane.stop(problem),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_kfused_remainder_tail(self, lanes):
        # (10 - 1) % 2 == 1: the batch runs the masked k=1 tail through
        # the SAME velocity-form kernel the solo march does.
        p10 = Problem(N=16, timesteps=10)
        res = eb.solve_ensemble(
            p10, lanes, scheme="compensated", path="kfused", k=2,
            interpret=True,
        )
        solos = [
            kfused_comp.solve_kfused_comp(
                p10, k=2, interpret=True, phase=lane.phase,
                stop_step=lane.stop(p10),
            )
            for lane in lanes
        ]
        _assert_lane_parity(res, solos)

    def test_masked_padding_leaves_real_lanes_bitwise_unchanged(
        self, problem, lanes
    ):
        plain = eb.solve_ensemble(
            problem, lanes, scheme="compensated", path="kfused", k=2,
            interpret=True,
        )
        padded = eb.solve_ensemble(
            problem, lanes, scheme="compensated", path="kfused", k=2,
            interpret=True, pad_to=8,
        )
        assert padded.batch_size == 8 and padded.n_lanes == 3
        for a, b in zip(padded.results, plain.results):
            assert _bitwise(a.u_cur, b.u_cur)
            assert _bitwise(a.u_prev, b.u_prev)
            assert np.array_equal(a.abs_errors, b.abs_errors)
            assert np.array_equal(a.rel_errors, b.rel_errors)

    def test_compensated_batches_on_every_path(self, problem):
        # Every path runs the compensated lane program itself: the
        # batch's raw state carries the lane axis, and each lane's
        # result is its row of it.
        for path, k in (("roll", 1), ("pallas", 1), ("kfused", 2)):
            res = eb.solve_ensemble(
                problem, [eb.LaneSpec()], scheme="compensated",
                path=path, k=k, interpret=True,
            )
            assert res.path == path and res.batch_size == 1
            assert np.shape(res.u_cur_batch) == (1,) + (problem.N,) * 3
            assert _bitwise(res.results[0].u_cur, res.u_cur_batch[0])
            assert np.isfinite(res.results[0].abs_errors).all()

    def test_compensated_field_batch_rejected(self, problem):
        field = np.full((problem.N,) * 3, problem.a2tau2)
        with pytest.raises(ValueError, match="compensated"):
            eb.solve_ensemble(
                problem, [eb.LaneSpec(c2tau2_field=field)],
                scheme="compensated", path="roll", compute_errors=False,
            )


class TestValidation:
    def test_empty_batch_rejected(self, problem):
        with pytest.raises(ValueError, match="at least one lane"):
            eb.solve_ensemble(problem, [], path="roll")

    def test_bad_path_rejected(self, problem):
        with pytest.raises(ValueError, match="path"):
            eb.solve_ensemble(problem, [eb.LaneSpec()], path="cuda")

    def test_stop_out_of_range(self, problem):
        with pytest.raises(ValueError, match="stop_step"):
            eb.solve_ensemble(
                problem, [eb.LaneSpec(stop_step=99)], path="roll"
            )

    def test_kfused_misaligned_stop_rejected(self, problem):
        # stop=4: (4-1) % 2 != 0 and 4 != timesteps -> a lane cannot
        # freeze mid-block.
        with pytest.raises(ValueError, match="k-block"):
            eb.solve_ensemble(
                problem, [eb.LaneSpec(stop_step=4)], path="kfused", k=2,
                interpret=True,
            )

    def test_kfused_k_must_divide_n(self, problem):
        with pytest.raises(ValueError, match="divide"):
            eb.solve_ensemble(
                problem, [eb.LaneSpec()], path="kfused", k=3,
                interpret=True,
            )

    def test_solo_solvers_reject_phase_with_variable_c(self, problem):
        # The solver-level twin of the lane check: a shifted phase has
        # no analytic layer-1 bootstrap under variable c, and the solo
        # APIs must refuse rather than silently initialize from the
        # constant-speed solution.
        field = np.full((problem.N,) * 3, problem.a2tau2)
        with pytest.raises(ValueError, match="analytic"):
            kfused.solve_kfused(
                problem, k=2, interpret=True, compute_errors=False,
                c2tau2_field=field, phase=1.0,
            )
        with pytest.raises(ValueError, match="analytic"):
            leapfrog.solve(
                problem,
                step_fn=stencil_ref.make_variable_c_step(field),
                compute_errors=False, phase=1.0,
            )


class TestPhaseAccuracy:
    """The phase-shifted IVP has nonzero initial velocity u_t(0) =
    -a_t sin(phase) Sx Sy Sz; without the tau * u_t(0) layer-1 term
    (leapfrog.phase_velocity_coeff) the solver integrates a DIFFERENT
    problem than the oracle measures and the reported "error" is O(1) -
    the serving-path defect this suite pins against regression."""

    def test_shifted_phase_errors_stay_discretization_small(self):
        p = Problem(N=32, timesteps=20)
        ref = leapfrog.solve(p).abs_errors.max()
        for ph in (1.0, 0.5, 5.98):
            e = leapfrog.solve(p, phase=ph).abs_errors.max()
            # without the velocity term these sit at 0.27-0.94 (O(1));
            # with it they are the same discretization class as the
            # reference phase (~1e-3 at N=32/20 f32)
            assert e < 10 * ref, f"phase={ph}: {e} vs ref {ref}"

    def test_kfused_shifted_phase_accuracy(self):
        p = Problem(N=32, timesteps=20)
        e = kfused.solve_kfused(
            p, k=4, interpret=True, phase=1.0
        ).abs_errors.max()
        assert e < 1e-2

    def test_default_phase_is_the_reference_program(self, problem):
        # phase=2*pi must be bit-identical to the phase-less call (the
        # velocity term is statically absent at the reference phase).
        a = leapfrog.solve(problem)
        b = leapfrog.solve(problem, phase=2.0 * np.pi)
        assert _bitwise(a.u_cur, b.u_cur)
        assert np.array_equal(a.abs_errors, b.abs_errors)


class TestResultShape:
    def test_aggregate_throughput_sums_lanes(self, problem, lanes):
        res = eb.solve_ensemble(problem, lanes, path="roll")
        cells = sum(
            problem.cells_per_step * lane.stop(problem) for lane in lanes
        )
        expect = cells / res.solve_seconds / 1e9
        assert res.aggregate_gcells_per_second == pytest.approx(expect)

    def test_error_arrays_trimmed_to_lane_stop(self, problem, lanes):
        res = eb.solve_ensemble(problem, lanes, path="roll")
        assert len(res.results[2].abs_errors) == 5 + 1
        assert res.results[2].steps_computed == 5

    def test_lane_results_are_the_batch_rows(self, problem, lanes):
        # The lanes come from one split of the padded batch and one
        # host copy of each error block: each is its own batch row.
        res = eb.solve_ensemble(problem, lanes, path="roll", pad_to=4)
        assert len(res.results) == 3
        for i, r in enumerate(res.results):
            assert _bitwise(r.u_prev, res.u_prev_batch[i])
            assert _bitwise(r.u_cur, res.u_cur_batch[i])
            for rows in (r.abs_errors, r.rel_errors):
                assert isinstance(rows, np.ndarray)
                assert rows.dtype == np.float64
                assert len(rows) == r.steps_computed + 1

    def test_lane_spec_defaults(self, problem):
        lane = eb.LaneSpec()
        assert lane.stop(problem) == problem.timesteps
        assert dataclasses.replace(lane, stop_step=3).stop(problem) == 3


def _refuse_build(*args, **kwargs):
    raise RuntimeError("vmapped program refused to build")


@pytest.mark.parametrize("entry", [
    "solve_ensemble", "solve_ensemble_sharded", "engine", "engine_mesh",
])
def test_build_failure_is_an_error(entry, monkeypatch):
    # A batched program that cannot be built is an error the caller
    # sees: no other path answers in its place.  The engine feeds it to
    # the circuit breaker, once, under the request's key.
    from wavetpu.ensemble import sharded as es
    from wavetpu.serve.engine import ServeEngine

    monkeypatch.setattr(eb, "EnsembleSolver", _refuse_build)
    monkeypatch.setattr(es, "ShardedEnsembleSolver", _refuse_build)
    p = Problem(N=8, timesteps=4)
    lanes = [eb.LaneSpec(), eb.LaneSpec(phase=1.0)]
    mesh = (2, 2, 1)
    with pytest.raises(RuntimeError, match="refused to build"):
        if entry == "solve_ensemble":
            eb.solve_ensemble(p, lanes, path="roll")
        elif entry == "solve_ensemble_sharded":
            es.solve_ensemble_sharded(p, lanes, mesh_shape=mesh)
        else:
            eng = ServeEngine(
                bucket_sizes=(2,), interpret=True, breaker_threshold=1,
                breaker_cooldown_s=60.0,
            )
            eng.solve(p, lanes, path="roll",
                      mesh=mesh if entry == "engine_mesh" else None)
    if entry.startswith("engine"):
        (row,) = eng.breaker_stats()["keys"]
        assert row["consecutive_failures"] == 1
        assert "refused to build" in row["last_error"]
        assert row["key"] == eng.breaker.describe(eng.breaker_key(
            p, "standard", "roll", 4, "f32", False,
            mesh if entry == "engine_mesh" else None,
        ))
