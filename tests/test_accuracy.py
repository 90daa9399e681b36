"""Accuracy observatory contracts (obs/accuracy.py, serve/shadow.py,
`wavetpu plan-report`).

The acceptance drill: a warmed server replaying a two-tier trace
(bf16-increment onion vs compensated f32) at --shadow-sample-rate 1.0
must yield a plan_table.json whose MEASURED frontier orders the two
plans correctly on BOTH axes - the bf16 plan faster, the compensated
plan >= 3 decades more accurate - with zero primary-path errors, zero
breaker events, and every shadow accounted for.  Around it: the
accuracy ledger's durability/foreign-line discipline (same contract as
obs/ledger.py), the shadow sampler's full eligibility/busy/chaos
matrix (a crashed shadow is a counter tick and nothing else), the
never-feeds-the-breaker pin at the scheduler seam, and the plan-table
join reproducing a known Pareto frontier from a fabricated ledger.
"""

import json
import os
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

from wavetpu.core.problem import Problem
from wavetpu.ensemble import batched as eb
from wavetpu.obs import accuracy, telemetry, tracing
from wavetpu.obs import ledger as compile_ledger
from wavetpu.obs.registry import MetricsRegistry
from wavetpu.run import faults
from wavetpu.serve.scheduler import DynamicBatcher, SolveRequest
from wavetpu.serve.shadow import ShadowSampler


# ---- plan identity ----

class TestPlanIdentity:
    def test_n_bucket_rounds_up_to_power_of_two(self):
        assert accuracy.n_bucket(1) == 1
        assert accuracy.n_bucket(2) == 2
        assert accuracy.n_bucket(3) == 4
        assert accuracy.n_bucket(100) == 128
        assert accuracy.n_bucket(120) == 128  # shares 100's bucket
        assert accuracy.n_bucket(512) == 512

    def test_make_plan_forces_k_1_off_the_onion(self):
        assert accuracy.make_plan("standard", "roll", 4, "f32")["k"] == 1
        assert accuracy.make_plan(
            "compensated", "kfused", 4, "f32"
        )["k"] == 4

    def test_normalize_plan_rejects_unknown_and_missing(self):
        plan = accuracy.make_plan("standard", "roll", 1, "f32")
        with pytest.raises(ValueError, match="unknown plan field"):
            accuracy.normalize_plan(dict(plan, bogus=1))
        with pytest.raises(ValueError, match="missing plan field"):
            accuracy.normalize_plan({"scheme": "standard"})

    def test_dtype_name_mapping(self):
        assert accuracy.dtype_name("float32") == "f32"
        assert accuracy.dtype_name("bfloat16") == "bf16"
        assert accuracy.dtype_name(np.dtype(np.float64)) == "f64"
        # a foreign dtype passes through instead of crashing the seam
        assert accuracy.dtype_name("int8") == "int8"


def _plan(**over):
    base = dict(scheme="standard", path="kfused", k=4, dtype="bf16",
                with_field=False)
    base.update(over)
    return base


# ---- ledger durability ----

class TestAccuracyLedgerDurability:
    def test_round_trip_across_two_process_lifetimes(self, tmp_path):
        p = str(tmp_path / accuracy.ACCURACY_FILENAME)
        led = accuracy.AccuracyLedger(p)
        led.record(_plan(), 512, 1000, 0.66, 2.19, 1.35e11,
                   ts=1.0, pid=111)
        led.close()
        led2 = accuracy.AccuracyLedger(p)  # "restart": appends
        led2.record(_plan(scheme="compensated", dtype="f32"),
                    100, 50, 5.7e-6, 8.0, 5.2e7,
                    source="shadow", ts=2.0, pid=222)
        led2.close()
        recs = accuracy.load_accuracy_ledger(p)
        assert len(recs) == 2
        assert recs[0]["plan"] == accuracy.normalize_plan(_plan())
        assert recs[0]["max_abs_err"] == 0.66
        assert recs[0]["n_bucket"] == 512
        assert recs[0]["source"] == "oracle"
        assert recs[1]["n_bucket"] == 128  # N=100 rounds up
        assert recs[1]["source"] == "shadow"
        assert [r["pid"] for r in recs] == [111, 222]

    def test_foreign_and_malformed_lines_skipped(self, tmp_path, capsys):
        """Junk in the append-only file - non-JSON, a foreign record
        type, a plan a future wavetpu wrote, a non-numeric error - is
        skipped and counted, never a crash."""
        p = str(tmp_path / accuracy.ACCURACY_FILENAME)
        led = accuracy.AccuracyLedger(p)
        led.record(_plan(), 64, 48, 0.5, 1.0, 1e7, ts=1.0, pid=1)
        led.close()
        with open(p, "a") as f:
            f.write("not json\n")
            f.write(json.dumps({"type": "compile", "key": {}}) + "\n")
            f.write(json.dumps({
                "type": "accuracy", "plan": dict(_plan(), novel="x"),
                "n": 64, "max_abs_err": 1.0,
            }) + "\n")
            f.write(json.dumps({
                "type": "accuracy", "plan": _plan(), "n": 64,
                "max_abs_err": "NaNish",
            }) + "\n")
        recs = accuracy.load_accuracy_ledger(p)
        assert len(recs) == 1
        assert "skipped 4 malformed" in capsys.readouterr().err
        # the report CLI survives the same file
        assert accuracy.main([p]) == 0
        capsys.readouterr()

    def test_unconfigured_record_is_zero_file_io(self, tmp_path,
                                                 monkeypatch):
        monkeypatch.chdir(tmp_path)
        accuracy.disable()
        assert not accuracy.enabled()
        accuracy.record_accuracy(_plan(), 64, 48, 0.5, 1.0, 1e7)
        assert list(tmp_path.iterdir()) == []

    def test_telemetry_configures_and_stops_ledger(self, tmp_path):
        d = str(tmp_path / "tel")
        tel = telemetry.start(d, interval=60.0)
        try:
            assert accuracy.enabled()
            assert accuracy.get_ledger().path == os.path.join(
                d, accuracy.ACCURACY_FILENAME
            )
        finally:
            tel.stop()
        assert not accuracy.enabled()

    def test_exempt_from_telemetry_rotation(self, tmp_path):
        """Same durability clause as the compile ledger: a tiny
        max_bytes rotates trace.jsonl while accuracy_ledger.jsonl
        keeps every entry in one un-rotated file."""
        d = str(tmp_path / "tel")
        tel = telemetry.start(d, interval=60.0, max_bytes=512, keep=2)
        try:
            for i in range(40):
                tracing.event("spam", i=i, pad="x" * 64)
                accuracy.record_accuracy(
                    _plan(), 64, i + 1, 0.5, 1.0, 1e7
                )
        finally:
            tel.stop()
        assert os.path.exists(os.path.join(d, "trace.jsonl.1"))
        lp = os.path.join(d, accuracy.ACCURACY_FILENAME)
        assert not os.path.exists(lp + ".1")
        recs = accuracy.load_accuracy_ledger(lp)
        assert len(recs) == 40
        assert [r["timesteps"] for r in recs] == list(range(1, 41))


# ---- metric stamps ----

class TestErrorMetrics:
    def test_oracle_and_shadow_signals_never_collide(self):
        reg = MetricsRegistry()
        plan = _plan(scheme="compensated", path="kfused", dtype="f32")
        accuracy.record_error_metrics(reg, plan, 1e-5)
        accuracy.record_error_metrics(reg, plan, 3e-3, shadow=True)
        labels = dict(path="kfused", scheme="compensated", dtype="f32")
        assert reg.gauge(
            "wavetpu_solve_max_abs_err", "", ("path", "scheme", "dtype")
        ).value(**labels) == 1e-5
        assert reg.gauge(
            "wavetpu_shadow_divergence", "", ("path", "scheme", "dtype")
        ).value(**labels) == 3e-3

    def test_solver_entry_point_records_measured_error(self, tmp_path):
        """The instrumented-solver seam end to end: a tiny solve with
        telemetry live appends one oracle line whose max_abs_err is
        exactly the result's measured maximum."""
        from wavetpu.solver import leapfrog

        d = str(tmp_path / "tel")
        problem = Problem(N=8, timesteps=4)
        tel = telemetry.start(d, interval=60.0)
        try:
            res = leapfrog.solve(problem)
        finally:
            tel.stop()
        recs = accuracy.load_accuracy_ledger(
            os.path.join(d, accuracy.ACCURACY_FILENAME)
        )
        mine = [r for r in recs if r["n"] == 8]
        assert len(mine) == 1
        assert mine[0]["max_abs_err"] == float(res.abs_errors.max())
        assert mine[0]["timesteps"] == 4
        assert mine[0]["source"] == "oracle"

    def test_oracle_skipped_means_nothing_recorded(self, tmp_path):
        from wavetpu.solver import leapfrog

        d = str(tmp_path / "tel")
        tel = telemetry.start(d, interval=60.0)
        try:
            leapfrog.solve(Problem(N=8, timesteps=4),
                           compute_errors=False)
        finally:
            tel.stop()
        lp = os.path.join(d, accuracy.ACCURACY_FILENAME)
        assert (not os.path.exists(lp)
                or accuracy.load_accuracy_ledger(lp) == [])


# ---- shadow sampler (unit: fabricated batcher) ----

class _StubFuture:
    def __init__(self, fn):
        self._fn = fn

    def result(self, timeout=None):
        return self._fn()


class _StubBatcher:
    """Deterministic twin: returns a fixed reference array (or an
    error), optionally blocking until released - enough surface for
    every ShadowSampler path without a real engine."""

    def __init__(self, ref, error=None, release=None):
        self.ref = ref
        self.error = error
        self.release = release
        self.submits = []

    def submit(self, req, request_id=None, deadline=None,
               trace_context=None):
        self.submits.append(req)

        def run():
            if self.release is not None:
                assert self.release.wait(30.0)
            if self.error is not None:
                return None, self.error, {}
            return (
                types.SimpleNamespace(u_cur=self.ref),
                None,
                {},
            )

        return _StubFuture(run)


def _shadow_req(problem=None, **over):
    kw = dict(scheme="standard", path="kfused", k=2, dtype_name="f32")
    kw.update(over)
    return SolveRequest(
        problem=problem or Problem(N=8, timesteps=4),
        lane=kw.pop("lane", eb.LaneSpec()), **kw
    )


def _lane_result(u, solve_seconds=0.02):
    return types.SimpleNamespace(
        u_cur=u, solve_seconds=solve_seconds, steps_computed=None
    )


class TestShadowSampler:
    def test_rate_bounds_validated(self):
        reg = MetricsRegistry()
        for bad in (-0.1, 1.5):
            with pytest.raises(ValueError, match="shadow-sample-rate"):
                ShadowSampler(_StubBatcher(None), reg, bad)

    def test_eligibility_matrix(self):
        s = ShadowSampler(_StubBatcher(None), MetricsRegistry(), 1.0)
        assert s.ineligible_reason(
            _shadow_req(resume_token="tok")
        ) == "resume"
        assert s.ineligible_reason(
            _shadow_req(mesh_shape=(2, 1, 1))
        ) == "mesh"
        assert s.ineligible_reason(_shadow_req(
            scheme="compensated", path="roll", k=1
        )) == "reference-plan"
        # the onion keeps its k, so compensated kfused is NOT reference
        assert s.ineligible_reason(_shadow_req(
            scheme="compensated", path="kfused", k=4
        )) is None
        assert s.ineligible_reason(_shadow_req()) is None

    def test_reference_request_shape(self):
        s = ShadowSampler(_StubBatcher(None), MetricsRegistry(), 1.0)
        req = _shadow_req(dtype_name="bf16", priority="interactive")
        ref = s.reference_request(req)
        assert (ref.scheme, ref.path, ref.k, ref.dtype_name) == (
            "compensated", "roll", 1, "f32"
        )
        assert ref.priority == "best_effort"
        assert ref.shadow is True
        assert ref.problem is req.problem
        # a c2-field lane keeps the standard scheme (no compensated
        # field variant) - still the f32 roll reference
        field_req = _shadow_req(
            lane=eb.LaneSpec(c2tau2_field=np.ones((9, 9, 9)))
        )
        assert s.reference_request(field_req).scheme == "standard"

    def test_rate_zero_skips_unsampled(self):
        reg = MetricsRegistry()
        s = ShadowSampler(_StubBatcher(None), reg, 0.0)
        assert s.offer(_shadow_req(), _lane_result(np.zeros(3)),
                       "r1") is False
        assert s.snapshot()["skipped"] == {"unsampled": 1.0}

    def test_divergence_measured_and_ledgered(self, tmp_path):
        """The divergence math pinned: served differs from the twin by
        exactly 0.5 in one cell -> L-inf divergence 0.5, recorded under
        the SERVED plan with source=shadow."""
        d = str(tmp_path / "tel")
        ref = np.zeros((4, 4, 4), dtype=np.float32)
        served = ref.copy()
        served[1, 2, 3] = 0.5
        reg = MetricsRegistry()
        batcher = _StubBatcher(ref)
        s = ShadowSampler(batcher, reg, 1.0, deadline_s=30.0)
        tel = telemetry.start(d, interval=60.0)
        try:
            assert s.offer(_shadow_req(), _lane_result(served),
                           "req-1") is True
            assert s.wait_idle(30.0)
        finally:
            tel.stop()
        snap = s.snapshot()
        assert snap["solves"] == 1.0 and snap["failures"] == 0.0
        assert reg.gauge(
            "wavetpu_shadow_divergence", "", ("path", "scheme", "dtype")
        ).value(path="kfused", scheme="standard", dtype="f32") == 0.5
        recs = accuracy.load_accuracy_ledger(
            os.path.join(d, accuracy.ACCURACY_FILENAME)
        )
        shadows = [r for r in recs if r["source"] == "shadow"]
        assert len(shadows) == 1
        assert shadows[0]["max_abs_err"] == 0.5
        # the SERVED plan, not the reference twin's
        assert shadows[0]["plan"]["path"] == "kfused"
        assert shadows[0]["plan"]["k"] == 2
        # the twin request the batcher saw was the reference plan
        assert batcher.submits[0].scheme == "compensated"
        assert batcher.submits[0].shadow is True

    def test_one_in_flight_second_offer_skipped_busy(self):
        release = threading.Event()
        ref = np.zeros(3, dtype=np.float32)
        reg = MetricsRegistry()
        s = ShadowSampler(_StubBatcher(ref, release=release), reg, 1.0)
        try:
            assert s.offer(_shadow_req(), _lane_result(ref), "a") is True
            assert s.offer(_shadow_req(), _lane_result(ref), "b") is False
            assert s.snapshot()["skipped"] == {"busy": 1.0}
        finally:
            release.set()
        assert s.wait_idle(30.0)
        assert s.snapshot()["solves"] == 1.0

    def test_shadow_fail_chaos_is_counter_only(self, tmp_path):
        """`WAVETPU_FAULT=serve-shadow-fail` kills the shadow worker
        BEFORE the twin is submitted: failure counted, no twin solve,
        no ledger line, and the next shadow (fault exhausted) runs
        clean."""
        d = str(tmp_path / "tel")
        ref = np.zeros(3, dtype=np.float32)
        batcher = _StubBatcher(ref)
        reg = MetricsRegistry()
        plan = faults.parse_serve_spec("serve-shadow-fail:count=1")
        s = ShadowSampler(batcher, reg, 1.0, fault_plan=plan)
        tel = telemetry.start(d, interval=60.0)
        try:
            assert s.offer(_shadow_req(), _lane_result(ref), "a") is True
            assert s.wait_idle(30.0)
            snap = s.snapshot()
            assert snap["failures"] == 1.0 and snap["solves"] == 0.0
            assert batcher.submits == []  # died before the twin
            # fault exhausted: the next sample succeeds
            assert s.offer(_shadow_req(), _lane_result(ref), "b") is True
            assert s.wait_idle(30.0)
        finally:
            tel.stop()
        assert s.snapshot()["solves"] == 1.0
        recs = accuracy.load_accuracy_ledger(
            os.path.join(d, accuracy.ACCURACY_FILENAME)
        )
        assert len([r for r in recs if r["source"] == "shadow"]) == 1

    def test_unhealthy_twin_is_a_failure_not_a_crash(self):
        reg = MetricsRegistry()
        ref = np.zeros(3, dtype=np.float32)
        s = ShadowSampler(_StubBatcher(ref, error="lane blew up"),
                          reg, 1.0)
        assert s.offer(_shadow_req(), _lane_result(ref), "a") is True
        assert s.wait_idle(30.0)
        snap = s.snapshot()
        assert snap["failures"] == 1.0 and snap["solves"] == 0.0


class _BreakerProbeEngine:
    """Records exactly what the scheduler passed for feed_breaker:
    'absent' = the production calling convention (stand-ins with the
    plain signature keep working), False = the shadow-only bypass."""

    max_batch = 4

    def __init__(self):
        self.feed_breaker_seen = []

    def solve(self, problem, lanes, scheme, path, k, dtype_name,
              mesh=None, timing=None, **kw):
        self.feed_breaker_seen.append(kw.get("feed_breaker", "absent"))
        if timing is not None:
            timing["compile_seconds"] = 0.0
            timing["warm"] = "true"
        results = [
            types.SimpleNamespace(steps_computed=problem.timesteps)
            for _ in lanes
        ]
        res = types.SimpleNamespace(
            results=results, n_lanes=len(lanes), batch_size=len(lanes),
            path=path, masked=False,
            solve_seconds=0.01, aggregate_gcells_per_second=1.0,
        )
        return res, [None] * len(lanes)


class TestShadowNeverFeedsBreaker:
    def test_scheduler_bypasses_breaker_for_shadow_only_batches(self):
        eng = _BreakerProbeEngine()
        b = DynamicBatcher(eng, max_wait=0.01)
        p = Problem(N=8, timesteps=4)
        try:
            b.submit(SolveRequest(problem=p, lane=eb.LaneSpec())).result(30)
            b.submit(SolveRequest(
                problem=p, lane=eb.LaneSpec(), shadow=True,
                priority="best_effort",
            )).result(30)
        finally:
            b.close()
        assert eng.feed_breaker_seen == ["absent", False]


# ---- plan table / plan-report ----

def _acc_rec(plan, err, wall, cells, n=64, source="oracle"):
    return {
        "type": "accuracy", "ts": 1.0, "pid": 1,
        "plan": accuracy.normalize_plan(plan), "n": n,
        "n_bucket": accuracy.n_bucket(n), "timesteps": 48,
        "max_abs_err": err, "wall_s": wall, "cells": cells,
        "source": source,
    }


class TestPlanTable:
    def _two_plan_ledger(self):
        """A fabricated frontier with a KNOWN shape: the bf16 onion is
        fast/inaccurate, compensated f32 is slow/accurate (both
        non-dominated), and a third plan slower AND less accurate than
        compensated is Pareto-dominated."""
        fast = _plan()  # standard:kfused k=4 bf16
        slow = _plan(scheme="compensated", path="roll", k=1,
                     dtype="f32")
        dead = _plan(scheme="standard", path="roll", k=1, dtype="f32")
        recs = []
        for w in (0.5, 0.6, 0.7):
            recs.append(_acc_rec(fast, 0.6 + w / 10, w, 1e9))
        for w in (2.0, 2.2, 2.4):
            recs.append(_acc_rec(slow, 1e-5, w, 1e9))
        recs.append(_acc_rec(dead, 1e-3, 4.0, 1e9))
        return recs, fast, slow, dead

    def test_known_pareto_frontier_reproduced(self):
        recs, fast, slow, dead = self._two_plan_ledger()
        table = accuracy.build_plan_table(recs)
        assert table[accuracy.PLAN_TABLE_FLAG] is True
        assert table["entries"] == 7
        rows = {accuracy.canonical_plan(r["plan"]): r
                for r in table["rows"]}
        frow = rows[accuracy.canonical_plan(fast)]
        srow = rows[accuracy.canonical_plan(slow)]
        drow = rows[accuracy.canonical_plan(dead)]
        # the two real plans span the frontier; the third is dominated
        assert frow["pareto_dominated"] is False
        assert srow["pareto_dominated"] is False
        assert drow["pareto_dominated"] is True
        # measured medians, exactly
        assert frow["wall_s_per_request"] == 0.6
        assert srow["wall_s_per_request"] == 2.2
        assert frow["gcells_per_s"] == round(1e9 / 0.6 / 1e9, 6)
        assert srow["err_p50"] == 1e-5
        assert frow["err_max"] == pytest.approx(0.67)
        assert frow["requests"] == 3 and frow["oracle_requests"] == 3

    def test_buckets_isolate_dominance(self):
        """Dominance is judged within an N-bucket only: a plan beaten
        at N=64 still stands alone in its own bucket."""
        fast = _plan()
        recs = [
            _acc_rec(fast, 0.6, 0.5, 1e9, n=64),
            _acc_rec(_plan(dtype="f32"), 1e-3, 0.4, 1e9, n=64),
            _acc_rec(fast, 0.6, 4.0, 1e9, n=300),  # alone in 512
        ]
        table = accuracy.build_plan_table(recs)
        by_bucket = {(accuracy.canonical_plan(r["plan"]), r["n_bucket"]):
                     r["pareto_dominated"] for r in table["rows"]}
        assert by_bucket[(accuracy.canonical_plan(fast), 64)] is True
        assert by_bucket[(accuracy.canonical_plan(fast), 512)] is False

    def test_shadow_lines_counted_and_mixed_into_percentiles(self):
        plan = _plan()
        recs = [
            _acc_rec(plan, 0.1, 1.0, 1e9),
            _acc_rec(plan, 0.3, 1.0, 1e9, source="shadow"),
        ]
        row = accuracy.build_plan_table(recs)["rows"][0]
        assert row["oracle_requests"] == 1
        assert row["shadow_requests"] == 1
        assert row["err_max"] == 0.3

    def test_compile_ledger_join(self):
        plan = _plan(scheme="compensated", dtype="f32")
        key = dict(N=64, Lx=1.0, Ly=1.0, Lz=1.0, T=1.0, timesteps=48,
                   scheme="compensated", path="kfused", k=4,
                   dtype="f32", with_field=False, compute_errors=True,
                   batch=1, mesh=None)
        compiles = [
            {"type": "compile", "key": key, "compile_s": 7.5,
             "cold": True},
            {"type": "compile", "key": key, "compile_s": 2.5,
             "cold": False},
            # disk loads are cache hits, not compiles - excluded
            {"type": "compile", "key": key, "compile_s": 0.2,
             "cold": True, "source": "disk"},
        ]
        row = accuracy.build_plan_table(
            [_acc_rec(plan, 1e-5, 1.0, 1e9)], compiles
        )["rows"][0]
        assert row["compiles"] == 2
        assert row["compile_s"] == 10.0

    def test_report_cli_text_json_and_emitted_table(self, tmp_path,
                                                    capsys):
        recs, fast, slow, dead = self._two_plan_ledger()
        d = str(tmp_path)
        with open(os.path.join(d, accuracy.ACCURACY_FILENAME),
                  "w") as f:
            for r in recs:
                f.write(json.dumps(r) + "\n")
        assert accuracy.main([d]) == 0
        out = capsys.readouterr().out
        assert "7 measured solve(s)" in out
        assert "3 (plan, N-bucket) frontier row(s)" in out
        assert "fleet/quota.py" in out  # the quota pricing pointer
        assert accuracy.main([d, "--json"]) == 0
        table = json.loads(capsys.readouterr().out)
        assert table[accuracy.PLAN_TABLE_FLAG] is True
        tpath = str(tmp_path / "plan_table.json")
        assert accuracy.main([d, "--emit-plan-table", tpath]) == 0
        capsys.readouterr()
        with open(tpath) as f:
            emitted = json.load(f)
        assert emitted[accuracy.PLAN_TABLE_FLAG] is True
        assert len(emitted["rows"]) == 3

    def test_report_cli_usage_errors(self, tmp_path, capsys):
        assert accuracy.main([]) == 2
        assert accuracy.main(["--bogus"]) == 2
        assert accuracy.main([str(tmp_path / "missing.jsonl")]) == 2
        capsys.readouterr()


# ---- loadgen error-budget loop ----

class TestLoadgenErrorBudget:
    def _report(self, errs_by_tier, budgets=None):
        from wavetpu.loadgen import report as lg_report
        from wavetpu.loadgen.runner import ReplayResult, RequestOutcome

        outs = []
        for tier, errs in errs_by_tier.items():
            for i, e in enumerate(errs):
                outs.append(RequestOutcome(
                    index=len(outs), scenario=tier, request_id=f"{tier}{i}",
                    status=200, latency_s=0.1, t_sent=0.0,
                    max_abs_error=e,
                ))
        result = ReplayResult(
            outcomes=outs, warmup_outcomes=[], metrics_before={},
            metrics_after={}, wall_seconds=1.0, mode="sequential",
            concurrency=1, speed=1.0,
        )
        return lg_report.build_report(result, error_budgets=budgets)

    def test_tier_rows_carry_measured_error_and_budget(self):
        rep = self._report(
            {"comp": [1e-6, 5e-6], "blind": [None, None]},
            budgets={"comp": 1e-5},
        )
        tiers = rep["tiers"]
        assert tiers["comp"]["max_abs_err"] == 5e-6
        assert tiers["comp"]["measured_requests"] == 2
        assert tiers["comp"]["error_budget"] == 1e-5
        # an oracle-less tier keeps the baseline row shape
        assert "max_abs_err" not in tiers["blind"]

    def test_error_slo_gate_passes_and_fails(self):
        from wavetpu.loadgen import report as lg_report

        rep = self._report({"comp": [1e-6, 5e-6], "blind": [None]})
        ok = lg_report.gate(rep, slo={"error_slos": {"comp": 1e-5}})
        assert ok == []
        bad = lg_report.gate(rep, slo={"error_slos": {"comp": 1e-9}})
        assert [v["slo"] for v in bad] == ["err:comp"]
        # a tier with no measured errors cannot claim to meet a budget
        blind = lg_report.gate(rep, slo={"error_slos": {"blind": 1e-3}})
        assert [v["slo"] for v in blind] == ["err:blind"]
        missing = lg_report.gate(rep, slo={"error_slos": {"nope": 1.0}})
        assert [v["slo"] for v in missing] == ["err:nope"]

    def test_error_slo_flag_parsing(self):
        from wavetpu.loadgen.cli import _parse_error_slos

        assert _parse_error_slos(["a=1e-3", "b=0.5"]) == {
            "a": 1e-3, "b": 0.5
        }
        with pytest.raises(ValueError, match="TIER=BUDGET"):
            _parse_error_slos(["nobudget"])


# ---- HTTP end to end ----

def _post(base, body, timeout=300):
    req = urllib.request.Request(
        base + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return r.status, json.loads(r.read())


def _wait_shadow(state, n, timeout=300.0):
    """The offer fires AFTER the primary bytes are on the wire, so the
    client can observe its 200 before the shadow thread exists - poll
    until n shadows have resolved (solved or failed), then join."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        snap = state.shadow.snapshot()
        if snap["solves"] + snap["failures"] >= n:
            assert state.shadow.wait_idle(timeout)
            return snap
        time.sleep(0.05)
    raise AssertionError(
        f"shadow never resolved {n} sample(s): {state.shadow.snapshot()}"
    )


def _serve(tmp_path, **kw):
    from wavetpu.serve.api import build_server

    kw.setdefault("port", 0)
    kw.setdefault("max_wait", 0.1)
    kw.setdefault("default_kernel", "roll")
    kw.setdefault("interpret", True)
    httpd, state = build_server(**kw)
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    return httpd, state, base


class TestServeShadowHTTP:
    def test_sampled_request_shadowed_and_ledgered(self, tmp_path):
        d = str(tmp_path / "tel")
        tel = telemetry.start(d, interval=60.0)
        httpd, state, base = _serve(tmp_path, shadow_sample_rate=1.0)
        try:
            code, body = _post(base, {"N": 8, "timesteps": 4})
            assert code == 200 and body["status"] == "ok"
            _wait_shadow(state, 1)
            _, metrics = _get(base, "/metrics")
            assert metrics["shadow"]["rate"] == 1.0
            assert metrics["shadow"]["solves"] == 1
            assert metrics["shadow"]["failures"] == 0
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()
            tel.stop()
        recs = accuracy.load_accuracy_ledger(
            os.path.join(d, accuracy.ACCURACY_FILENAME)
        )
        shadows = [r for r in recs if r["source"] == "shadow"]
        assert len(shadows) == 1
        # divergence of the served standard plan vs the compensated
        # twin: two different f32 rounding paths, so tiny but bounded
        assert 0.0 <= shadows[0]["max_abs_err"] < 1e-3
        assert shadows[0]["plan"]["scheme"] == "standard"
        # oracle lines landed too: the primary lane AND the twin lane
        oracles = [r for r in recs if r["source"] == "oracle"]
        assert len(oracles) >= 2

    def test_shadow_crash_invisible_to_primary_and_breaker(self,
                                                           tmp_path):
        """The chaos drill: with serve-shadow-fail armed, the primary
        answer is numerically identical to the clean run's, the
        breaker records nothing, and the failure is one counter tick."""
        plan = faults.parse_serve_spec("serve-shadow-fail:count=1")
        httpd, state, base = _serve(
            tmp_path, shadow_sample_rate=1.0, fault_plan=plan,
        )
        try:
            body = {"N": 8, "timesteps": 4}
            code1, p1 = _post(base, body)
            assert code1 == 200
            _wait_shadow(state, 1)
            _, m1 = _get(base, "/metrics")
            assert m1["shadow"]["failures"] == 1
            assert m1["shadow"]["solves"] == 0
            assert m1["breaker"]["enabled"] is True
            assert m1["breaker"]["open"] == 0
            assert m1["breaker"]["keys"] == []
            # fault exhausted: same request again, clean shadow
            code2, p2 = _post(base, body)
            assert code2 == 200
            _wait_shadow(state, 2)
            # primary answers are numerically identical - the crashed
            # shadow touched nothing
            assert p1["report"]["abs_errors"] == p2["report"]["abs_errors"]
            assert (p1["report"]["max_abs_error"]
                    == p2["report"]["max_abs_error"])
            _, m2 = _get(base, "/metrics")
            assert m2["shadow"]["solves"] == 1
            assert m2["responses_error"] == 0
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()

    def test_ineligible_reference_plan_not_shadowed(self, tmp_path):
        httpd, state, base = _serve(tmp_path, shadow_sample_rate=1.0)
        try:
            code, _ = _post(
                base, {"N": 8, "timesteps": 4, "scheme": "compensated"}
            )
            assert code == 200
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if state.shadow.snapshot()["skipped"]:
                    break
                time.sleep(0.05)
            _, metrics = _get(base, "/metrics")
            assert metrics["shadow"]["solves"] == 0
            assert metrics["shadow"]["skipped"] == {
                "reference-plan": 1
            }
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()


@pytest.mark.slow
class TestTwoTierDrill:
    def test_measured_frontier_orders_both_axes(self, tmp_path):
        """The pinned acceptance drill: a warmed server replays a
        two-tier trace (bf16-increment k=4 onion vs compensated f32
        onion, N=64/T=48 - the size where the trade is real on CPU)
        at --shadow-sample-rate 1.0.  The resulting plan_table.json
        must order the plans correctly on BOTH measured axes (bf16
        faster, compensated >= 3 decades more accurate), with zero
        primary-path errors, zero breaker events, and shadows > 0."""
        d = str(tmp_path / "tel")
        tel = telemetry.start(d, interval=60.0)
        httpd, state, base = _serve(
            tmp_path, shadow_sample_rate=1.0, default_kernel="auto",
            max_wait=0.05,
        )
        bf16 = {"N": 64, "timesteps": 48, "fuse_steps": 4,
                "dtype": "bf16", "kernel": "pallas"}
        comp = {"N": 64, "timesteps": 48, "scheme": "compensated",
                "fuse_steps": 4, "kernel": "pallas"}
        try:
            # Three rounds per tier: each plan's first request carries
            # trace/compile overhead, and with only two samples the
            # nearest-rank p50 lands on that cold wall - where the two
            # tiers tie.  Three samples put the median on a warm solve.
            for i, body in enumerate((bf16, comp) * 3):
                code, payload = _post(base, body, timeout=600)
                assert code == 200 and payload["status"] == "ok"
                # one shadow in flight at a time: join before the next
                # tier so every sampled request really shadows
                _wait_shadow(state, i + 1, timeout=600.0)
            _, metrics = _get(base, "/metrics")
            assert metrics["responses_error"] == 0
            assert metrics["shadow"]["solves"] == 6
            assert metrics["shadow"]["failures"] == 0
            assert metrics["breaker"]["enabled"] is True
            assert metrics["breaker"]["open"] == 0
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()
            tel.stop()
        tpath = str(tmp_path / "plan_table.json")
        assert accuracy.main([d, "--emit-plan-table", tpath]) == 0
        with open(tpath) as f:
            table = json.load(f)
        assert table[accuracy.PLAN_TABLE_FLAG] is True
        rows = {
            (r["plan"]["scheme"], r["plan"]["dtype"]): r
            for r in table["rows"]
            if r["plan"]["path"] == "kfused" and r["n_bucket"] == 64
        }
        brow = rows[("standard", "bf16")]
        crow = rows[("compensated", "f32")]
        # each tier measured three times by the oracle + thrice by shadow
        assert brow["requests"] >= 3 and crow["requests"] >= 3
        # axis 1: the bf16 onion is measurably faster
        assert brow["gcells_per_s"] > crow["gcells_per_s"]
        assert brow["wall_s_per_request"] < crow["wall_s_per_request"]
        # axis 2: compensated f32 is >= 3 decades more accurate
        assert crow["err_p50"] * 1e3 <= brow["err_p50"]
        # the shadow reference twin (compensated roll) earns its own
        # measured row - proof the twin's oracle lines land in the table
        rrow = next(
            r for r in table["rows"]
            if r["plan"]["path"] == "roll" and r["n_bucket"] == 64
            and r["plan"]["scheme"] == "compensated"
        )
        assert rrow["requests"] >= 3
        # comp-kfused holds the strictly best measured error of the
        # three plans, so nothing can Pareto-dominate it
        assert crow["pareto_dominated"] is False
        # bf16's flag must agree with the table it sits in: dominated
        # iff some same-bucket row beats it on speed without losing on
        # error (on CPU interpret the jnp roll twin usually does)
        beats = any(
            r["gcells_per_s"] >= brow["gcells_per_s"]
            and r["err_p50"] <= brow["err_p50"]
            and (r["gcells_per_s"] > brow["gcells_per_s"]
                 or r["err_p50"] < brow["err_p50"])
            for r in table["rows"]
            if r["n_bucket"] == 64 and r is not brow
        )
        assert brow["pareto_dominated"] is beats
