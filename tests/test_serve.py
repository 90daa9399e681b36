"""Serve-layer contracts: engine cache, dynamic batcher, HTTP front end.

The acceptance-level smoke test drives CONCURRENT HTTP requests at a live
ThreadingHTTPServer and asserts they were coalesced into one batched
solve (batch occupancy > 1 observed via /metrics) with each request
receiving its own reference-format report - the end-to-end claim of
`wavetpu serve`.  The watchdog test pins per-lane blast-radius: a
Courant-unstable lane 422s while its batchmate's 200 stands.
"""

import json
import threading
import time
import types
import urllib.error
import urllib.request

import numpy as np
import pytest

from wavetpu.core.problem import Problem
from wavetpu.ensemble import batched as eb
from wavetpu.run import faults
from wavetpu.serve.api import _c2_preset, build_server, parse_solve_request
from wavetpu.serve.engine import ProgramKey, ServeEngine
from wavetpu.serve.preempt import SolveStateStore
from wavetpu.serve.resilience import (
    DeadlineExceededError,
    InvalidStateTokenError,
    PreemptedError,
)
from wavetpu.serve.scheduler import (
    DynamicBatcher,
    QueueFullError,
    ServeMetrics,
    SolveRequest,
)
from tests.test_obs import parse_prometheus


def _bitwise(a, b):
    return np.array_equal(np.asarray(a), np.asarray(b))


# ---- engine ----

class TestEngine:
    def test_bucket_for(self):
        eng = ServeEngine(bucket_sizes=(1, 2, 4, 8), interpret=True)
        assert eng.bucket_for(1) == 1
        assert eng.bucket_for(3) == 4
        assert eng.bucket_for(8) == 8
        with pytest.raises(ValueError, match="exceed"):
            eng.bucket_for(9)

    def test_program_cache_hits_misses_eviction(self):
        eng = ServeEngine(
            bucket_sizes=(1, 2), max_programs=1, interpret=True
        )
        p1 = Problem(N=8, timesteps=3)
        p2 = Problem(N=8, timesteps=4)
        a = eng.program(p1, "standard", "roll", 1, "f32", False, 2)
        assert a is not None and eng.misses == 1 and eng.hits == 0
        b = eng.program(p1, "standard", "roll", 1, "f32", False, 2)
        assert b is a and eng.hits == 1
        c = eng.program(p2, "standard", "roll", 1, "f32", False, 2)
        assert c is not a
        assert eng.evictions == 1
        stats = eng.cache_stats()
        assert stats["programs"] == 1
        assert stats["misses"] == 2

    def test_solve_pads_to_bucket(self):
        eng = ServeEngine(bucket_sizes=(1, 2, 4), interpret=True)
        p = Problem(N=8, timesteps=3)
        lanes = [eb.LaneSpec(), eb.LaneSpec(phase=1.0), eb.LaneSpec()]
        res, health = eng.solve(p, lanes, path="roll")
        assert res.batch_size == 4
        assert res.n_lanes == 3
        assert health == [None, None, None]

    def test_warmup_precompiles(self):
        eng = ServeEngine(bucket_sizes=(1, 2), interpret=True)
        p = Problem(N=8, timesteps=3)
        warmed = eng.warmup(p, path="roll")
        assert warmed == [1, 2]
        assert eng.misses == 2
        eng.solve(p, [eb.LaneSpec()], path="roll")
        assert eng.hits == 1  # served from the warmed program

    def test_compensated_scheme_batches_through_the_engine(self):
        # The flagship scheme rides the vmapped core: padded to the
        # bucket, each lane bitwise its solo solve.
        eng = ServeEngine(bucket_sizes=(1, 2, 4), interpret=True)
        p = Problem(N=8, timesteps=5)
        lanes = [eb.LaneSpec(), eb.LaneSpec(phase=1.0)]
        res, health = eng.solve(
            p, lanes, scheme="compensated", path="kfused", k=2
        )
        assert res.batch_size == 2 and health == [None, None]
        from wavetpu.solver import kfused_comp

        solo = kfused_comp.solve_kfused_comp(
            p, k=2, interpret=True, phase=1.0
        )
        assert _bitwise(res.results[1].u_cur, solo.u_cur)

    def test_sharded_batched_program_cached_per_mesh_bucket(self):
        eng = ServeEngine(bucket_sizes=(1, 2), interpret=True)
        p = Problem(N=8, timesteps=4)
        warmed = eng.warmup(p, path="roll", mesh=(2, 2, 1))
        assert warmed == [1, 2]
        res, health = eng.solve(
            p, [eb.LaneSpec(), eb.LaneSpec(phase=1.0)], path="roll",
            mesh=(2, 2, 1),
        )
        assert health == [None, None]
        assert eng.hits == 1  # served from the warmed (mesh, bucket=2)
        keys = eng.cache_stats()["keys"]
        assert any(tuple(k[-1] or ()) == (2, 2, 1) for k in keys)
        # parity of one lane vs the solo sharded solve
        from wavetpu.solver import sharded

        solo = sharded.solve_sharded(
            p, mesh_shape=(2, 2, 1), kernel="roll", phase=1.0
        )
        assert _bitwise(res.results[1].u_cur, solo.u_cur)

    def test_watchdog_isolates_poisoned_lane(self):
        # C = 0.55: stable under constant c^2 = a^2, but the two-layer
        # preset DOUBLES c^2 in half the domain (c * sqrt2 -> C = 0.78,
        # past the leapfrog bound) - that lane blows up while its
        # batchmate stays bounded.
        p = Problem(N=8, T=26.0, timesteps=60)
        eng = ServeEngine(bucket_sizes=(1, 2), interpret=True)
        lanes = [
            eb.LaneSpec(c2tau2_field=_c2_preset(p, "constant")),
            eb.LaneSpec(c2tau2_field=_c2_preset(p, "two-layer")),
        ]
        res, health = eng.solve(p, lanes, path="roll")
        assert health[0] is None
        assert health[1] is not None and "amax" in health[1]
        amax0 = float(np.abs(np.asarray(res.results[0].u_cur)).max())
        assert amax0 < 10.0  # the healthy lane is untouched

    def test_guarded_amax_per_lane_semantics(self):
        from wavetpu.run import health

        batch = np.stack([
            np.ones((4, 4, 4)),
            np.full((4, 4, 4), np.nan),
            np.full((4, 4, 4), 7.0),
        ])
        out = health.guarded_amax_per_lane(batch)
        assert out.shape == (3,)
        assert out[0] == 1.0
        assert np.isinf(out[1])  # NaN anywhere -> +inf, as guarded_amax
        assert out[2] == 7.0
        # agrees with the solo guard lane by lane
        for i in range(3):
            assert out[i] == health.guarded_amax(batch[i])

    def test_mesh_with_compensated_scheme_refused_loudly(self):
        # Silently serving a compensated request with the standard
        # scheme would be a wrong-result bug, not a fallback.
        eng = ServeEngine(bucket_sizes=(1,), interpret=True)
        p = Problem(N=8, timesteps=3)
        with pytest.raises(ValueError, match="standard scheme only"):
            eng.solve(
                p, [eb.LaneSpec()], scheme="compensated", path="roll",
                mesh=(2, 1, 1),
            )

    def test_watchdog_can_be_disabled(self):
        p = Problem(N=8, T=26.0, timesteps=60)
        eng = ServeEngine(
            bucket_sizes=(1,), interpret=True, watchdog=False,
        )
        _, health = eng.solve(
            p, [eb.LaneSpec(c2tau2_field=_c2_preset(p, "two-layer"))],
            path="roll",
        )
        assert health == [None]


# ---- scheduler (fake engine: batching logic only) ----

class _FakeEngine:
    """Engine stub recording batch compositions."""

    max_batch = 4

    def __init__(self, fail=False):
        self.batches = []
        self.fail = fail

    def solve(self, problem, lanes, scheme, path, k, dtype_name,
              mesh=None, timing=None):
        if self.fail:
            raise RuntimeError("engine exploded")
        if timing is not None:
            timing["compile_seconds"] = 0.0
            timing["warm"] = "true"
        self.batches.append(len(lanes))
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


def _req(problem, **kw):
    return SolveRequest(problem=problem, lane=eb.LaneSpec(**kw))


class TestBatcher:
    def test_concurrent_same_key_requests_coalesce(self):
        eng = _FakeEngine()
        metrics = ServeMetrics()
        b = DynamicBatcher(eng, metrics=metrics, max_wait=0.5)
        p = Problem(N=8, timesteps=3)
        futs = [b.submit(_req(p, phase=1.0 + i)) for i in range(3)]
        out = [f.result(10) for f in futs]
        b.close()
        assert eng.batches == [3]
        assert all(o[2]["occupancy"] == 3 for o in out)
        snap = metrics.snapshot()
        assert snap["batches_total"] == 1
        assert snap["batch_occupancy_max"] == 3

    def test_different_keys_never_share_a_batch(self):
        eng = _FakeEngine()
        b = DynamicBatcher(eng, max_wait=0.3)
        pa = Problem(N=8, timesteps=3)
        pb = Problem(N=8, timesteps=4)
        fa = b.submit(_req(pa))
        fb = b.submit(_req(pb))
        fa.result(10)
        fb.result(10)
        b.close()
        assert sorted(eng.batches) == [1, 1]

    def test_max_batch_closes_the_batch_early(self):
        eng = _FakeEngine()
        b = DynamicBatcher(eng, max_wait=30.0, max_batch=2)
        p = Problem(N=8, timesteps=3)
        t0 = time.monotonic()
        futs = [b.submit(_req(p, phase=1.0 + i)) for i in range(2)]
        for f in futs:
            f.result(10)
        took = time.monotonic() - t0
        b.close()
        assert eng.batches == [2]
        assert took < 5.0  # did not sit out the 30 s max_wait

    def test_engine_failure_propagates_to_every_future(self):
        b = DynamicBatcher(_FakeEngine(fail=True), max_wait=0.2)
        p = Problem(N=8, timesteps=3)
        futs = [b.submit(_req(p, phase=1.0 + i)) for i in range(2)]
        for f in futs:
            with pytest.raises(RuntimeError, match="engine exploded"):
                f.result(10)
        b.close()

    def test_bucket_key_separates_program_identities(self):
        p = Problem(N=8, timesteps=3)
        base = _req(p)
        assert base.bucket_key() == _req(p, phase=2.0).bucket_key()
        other = SolveRequest(problem=p, lane=eb.LaneSpec(), dtype_name="f64")
        assert base.bucket_key() != other.bucket_key()
        kf = SolveRequest(problem=p, lane=eb.LaneSpec(), path="kfused", k=2)
        assert base.bucket_key() != kf.bucket_key()
        meshy = SolveRequest(
            problem=p, lane=eb.LaneSpec(), mesh_shape=(2, 2, 1)
        )
        assert base.bucket_key() != meshy.bucket_key()


class TestLengthBuckets:
    """Length-bucketed scheduling: lanes with diverging stop_steps are
    sorted into step-length buckets (k-block-granular) before batching,
    so a short request never marches a long batch's masked tail."""

    def _kreq(self, p, stop, k=2):
        return SolveRequest(
            problem=p, lane=eb.LaneSpec(stop_step=stop), path="kfused",
            k=k,
        )

    def test_bucket_assignment_and_quantum(self):
        p = Problem(N=8, timesteps=40)
        b = DynamicBatcher(
            _FakeEngine(), max_wait=0.01, length_bucket_steps=10
        )
        try:
            # 1-step path: quantum 10, bucket = (stop-1)//10
            assert b.length_bucket(_req(p)) == 3  # stop=40
            r5 = SolveRequest(problem=p, lane=eb.LaneSpec(stop_step=5))
            r11 = SolveRequest(problem=p, lane=eb.LaneSpec(stop_step=11))
            assert b.length_bucket(r5) == 0
            assert b.length_bucket(r11) == 1
        finally:
            b.close()

    def test_quantum_rounds_up_to_k_block_grid(self):
        # quantum 10 with k=4 aligns to 12: every bucket boundary sits
        # on the onion's k-block grid ((stop-1) % k == 0 freeze points).
        p = Problem(N=8, timesteps=40)
        b = DynamicBatcher(
            _FakeEngine(), max_wait=0.01, length_bucket_steps=10
        )
        try:
            assert b.length_bucket(self._kreq(p, 13, k=4)) == 1  # 12//12
            assert b.length_bucket(self._kreq(p, 12 + 1, k=4)) == 1
            assert b.length_bucket(self._kreq(p, 9, k=4)) == 0
            assert b.length_bucket(self._kreq(p, 25, k=4)) == 2
        finally:
            b.close()

    def test_disabled_by_default_everything_one_bucket(self):
        p = Problem(N=8, timesteps=40)
        b = DynamicBatcher(_FakeEngine(), max_wait=0.01)
        try:
            r5 = SolveRequest(problem=p, lane=eb.LaneSpec(stop_step=5))
            assert b.length_bucket(r5) == 0
            assert b.length_bucket(_req(p)) == 0
        finally:
            b.close()

    def test_different_length_buckets_never_share_a_batch(self):
        eng = _FakeEngine()
        b = DynamicBatcher(eng, max_wait=0.3, length_bucket_steps=10)
        p = Problem(N=8, timesteps=40)
        fs = b.submit(SolveRequest(problem=p, lane=eb.LaneSpec(stop_step=5)))
        fl = b.submit(_req(p, phase=1.0))
        fs2 = b.submit(SolveRequest(problem=p, lane=eb.LaneSpec(stop_step=7)))
        out = [f.result(10) for f in (fs, fl, fs2)]
        b.close()
        # the two short requests coalesce; the long one runs alone
        assert sorted(eng.batches) == [1, 2]
        assert out[1][2]["occupancy"] == 1

    def test_starvation_bound_stashed_request_served_next_round(self):
        # A non-matching stashed request becomes the NEXT batch's leader
        # (arrival order), so it waits at most one batch - the bound the
        # occupancy/latency tradeoff rests on.
        eng = _FakeEngine()
        b = DynamicBatcher(eng, max_wait=0.2, length_bucket_steps=10)
        p = Problem(N=8, timesteps=40)
        f1 = b.submit(SolveRequest(problem=p, lane=eb.LaneSpec(stop_step=5)))
        f2 = b.submit(_req(p, phase=1.0))  # different bucket: stashed
        t0 = time.monotonic()
        f1.result(10)
        f2.result(10)
        took = time.monotonic() - t0
        b.close()
        assert eng.batches == [1, 1]
        assert took < 5.0


class TestDrain:
    def test_drain_resolves_queued_futures_with_results(self):
        eng = _FakeEngine()
        # max_wait far longer than the test: drain must flush
        # immediately, not sit out the window.
        b = DynamicBatcher(eng, max_wait=30.0, max_batch=2)
        p = Problem(N=8, timesteps=3)
        futs = [b.submit(_req(p, phase=1.0 + i)) for i in range(3)]
        t0 = time.monotonic()
        b.close(timeout=60.0, drain=True)
        took = time.monotonic() - t0
        for f in futs:
            res, health, info = f.result(0)  # already resolved
            assert health is None
        assert took < 10.0
        assert sum(eng.batches) == 3

    def test_drain_refuses_new_submits(self):
        b = DynamicBatcher(_FakeEngine(), max_wait=0.01)
        b.close(drain=True)
        p = Problem(N=8, timesteps=3)
        with pytest.raises(RuntimeError, match="closed"):
            b.submit(_req(p))

    def test_drain_timeout_fails_unserved_futures_without_stranding(self):
        # A drain that outlives its timeout must stop draining, and
        # close() must fail whatever the worker could not finish -
        # blocked handlers get an error, never the 600 s request
        # timeout.  The slow engine makes each batch outlast the drain
        # timeout deterministically.
        class _SlowEngine(_FakeEngine):
            def solve(self, *a, **k):
                time.sleep(1.0)
                return super().solve(*a, **k)

        eng = _SlowEngine()
        b = DynamicBatcher(eng, max_wait=30.0, max_batch=1)
        p = Problem(N=8, timesteps=3)
        futs = [b.submit(_req(p, phase=1.0 + i)) for i in range(4)]
        b.close(timeout=0.2, drain=True)
        resolved = errored = 0
        for f in futs:
            try:
                f.result(10)  # in-flight batches may still land
                resolved += 1
            except RuntimeError:
                errored += 1
        assert resolved + errored == 4
        assert errored >= 1  # the tail was failed, not stranded

    def test_drain_vs_submit_race_never_hangs(self):
        """A request submitted CONCURRENTLY with close(drain=True) must
        resolve - with a result or a fast shutdown error - never hang
        to the client timeout.  Hammer the race: a spammer thread
        submits as fast as it can while the main thread drains; every
        future it got back must be done shortly after close returns."""
        eng = _FakeEngine()
        b = DynamicBatcher(eng, max_wait=0.01, max_batch=4)
        p = Problem(N=8, timesteps=3)
        futs = []
        started = threading.Event()

        def spam():
            i = 0
            while True:
                try:
                    futs.append(b.submit(_req(p, phase=1.0 + i)))
                except RuntimeError:
                    return  # batcher closed: the race window is over
                i += 1
                started.set()

        th = threading.Thread(target=spam, daemon=True)
        th.start()
        assert started.wait(5)
        b.close(timeout=30.0, drain=True)
        th.join(10)
        assert not th.is_alive()
        assert futs  # the race actually happened
        deadline = time.monotonic() + 10.0
        resolved = errored = 0
        for f in futs:
            try:
                f.result(max(0.0, deadline - time.monotonic()))
                resolved += 1
            except RuntimeError:
                errored += 1
        # every single future resolved fast - results for what the
        # drain flushed, an immediate error for what raced past it
        assert resolved + errored == len(futs)
        assert resolved >= 1

    def test_close_without_drain_still_errors_stashed_leftovers(self):
        # The non-drain path keeps its contract: the in-flight batch
        # resolves, but a stashed different-key request fails fast
        # instead of hanging to the request timeout.
        eng = _FakeEngine()
        b = DynamicBatcher(eng, max_wait=30.0, max_batch=8)
        pa = Problem(N=8, timesteps=3)
        pb = Problem(N=8, timesteps=4)
        f1 = b.submit(_req(pa))
        f2 = b.submit(SolveRequest(problem=pb, lane=eb.LaneSpec()))
        b.close(timeout=10.0)
        res, health, info = f1.result(10)  # the batch in flight finishes
        assert health is None
        with pytest.raises(RuntimeError, match="shutting down"):
            f2.result(0)


class TestBoundedQueue:
    """Bounded request queue with 429 backpressure (ROADMAP serving-
    hardening item): submit() raises QueueFullError once max_queue
    requests are submitted-but-not-executing; depth and rejections are
    exposed via the registry and /metrics."""

    def test_submit_rejects_when_full(self):
        class _StuckEngine(_FakeEngine):
            def __init__(self):
                super().__init__()
                self.release = threading.Event()

            def solve(self, *a, **k):
                self.release.wait(30)
                return super().solve(*a, **k)

        eng = _StuckEngine()
        metrics = ServeMetrics()
        b = DynamicBatcher(eng, metrics=metrics, max_wait=30.0,
                           max_batch=1, max_queue=2)
        p = Problem(N=8, timesteps=3)
        try:
            # First fills the (max_batch=1) in-flight batch; the worker
            # takes it off the queue, so keep stuffing until depth
            # sticks at the bound, then the next submit must 429.
            futs = [b.submit(_req(p, phase=1.0 + i)) for i in range(2)]
            with pytest.raises(QueueFullError, match="queue full"):
                for i in range(8):
                    futs.append(b.submit(_req(p, phase=10.0 + i)))
            snap = metrics.snapshot()
            assert snap["rejected_total"] >= 1
            assert snap["queue_depth"] >= 1
        finally:
            eng.release.set()
            b.close(timeout=10.0, drain=True)

    def test_zero_max_queue_rejects_everything(self):
        b = DynamicBatcher(_FakeEngine(), max_wait=0.01, max_queue=0)
        p = Problem(N=8, timesteps=3)
        try:
            with pytest.raises(QueueFullError):
                b.submit(_req(p))
        finally:
            b.close()

    def test_unbounded_by_default(self):
        b = DynamicBatcher(_FakeEngine(), max_wait=0.2)
        assert b.max_queue is None
        p = Problem(N=8, timesteps=3)
        futs = [b.submit(_req(p, phase=1.0 + i)) for i in range(16)]
        for f in futs:
            f.result(10)
        b.close()

    def test_depth_returns_to_zero_after_service(self):
        metrics = ServeMetrics()
        b = DynamicBatcher(_FakeEngine(), metrics=metrics, max_wait=0.05)
        p = Problem(N=8, timesteps=3)
        b.submit(_req(p)).result(10)
        b.close()
        assert metrics.snapshot()["queue_depth"] == 0


class TestPreemptible:
    """The preemption drill (docs/robustness.md "Preemptible solves"):
    long solves march CHUNKED through the batcher, interrupted by each
    of {deadline, worker crash, drain} they resume - via resume token
    or in-memory progress - and the final state is BITWISE identical to
    the same solve run unpreempted.  Corrupt tokens 422 cleanly and the
    circuit breaker never hears about any of it."""

    THRESHOLD = 8
    CHUNK = 4

    @pytest.fixture(scope="class")
    def eng(self):
        # one real CPU engine for the whole class: the chunk programs
        # compile once, every test after the first runs warm
        return ServeEngine(bucket_sizes=(1,), interpret=True)

    def _batcher(self, eng, store=None, plan=None, max_wait=0.02):
        return DynamicBatcher(
            eng, max_wait=max_wait, fault_plan=plan,
            chunk_threshold=self.THRESHOLD, chunk_steps=self.CHUNK,
            state_store=store,
        )

    def _long(self, timesteps=17):
        return Problem(N=8, timesteps=timesteps)

    def _control(self, eng, p):
        """The unpreempted chunked march (the drill's parity baseline)."""
        b = self._batcher(eng)
        try:
            return b.submit(_req(p)).result(120)
        finally:
            b.close()

    def test_long_solve_marches_chunked_matching_monolithic(self, eng):
        p = self._long()
        res, health, info = self._control(eng, p)
        assert health is None
        assert info["chunked"] is True
        assert info["chunks"] == 4          # ceil(16 / 4)
        assert info["chunk_len"] == self.CHUNK
        assert info["resumed_from"] is None
        assert info["occupancy"] == 1 and info["batch_size"] == 1
        assert res.final_step == p.timesteps
        # parity with the monolithic (vmapped, batch-of-1) serve path:
        # the chunked march is a latency/preemption trade, never an
        # accuracy one
        mono, mono_health = eng.solve(p, [eb.LaneSpec()], path="roll")
        assert mono_health == [None]
        assert _bitwise(res.u_cur, mono.results[0].u_cur)
        assert _bitwise(res.u_prev, mono.results[0].u_prev)
        assert _bitwise(res.abs_errors, mono.results[0].abs_errors)

    def test_short_requests_stay_on_the_batched_path(self, eng):
        b = self._batcher(eng)
        try:
            res, health, info = b.submit(
                _req(Problem(N=8, timesteps=4))
            ).result(120)
            assert health is None
            assert not info.get("chunked")
        finally:
            b.close()

    def test_deadline_preempts_with_token_resume_is_bitwise(
        self, eng, tmp_path
    ):
        p = self._long()
        control = self._control(eng, p)[0]
        store = SolveStateStore(str(tmp_path / "state"))
        # the per-chunk slow injection stretches the march so the
        # budget expires mid-flight, deterministically
        plan = faults.parse_serve_spec(
            f"serve-slow-batch:seconds=0.25,timesteps={p.timesteps}"
        )
        b = self._batcher(eng, store=store, plan=plan)
        try:
            fut = b.submit(_req(p), deadline=time.monotonic() + 0.4)
            with pytest.raises(DeadlineExceededError) as ei:
                fut.result(120)
            token = ei.value.resume_token
            assert SolveStateStore.valid_token(token)
            snap = b.metrics.snapshot()
            assert snap["preempted_total"] == 1
        finally:
            b.close()
        # resume on a FRESH batcher (same store), no budget this time
        b2 = self._batcher(eng, store=store)
        try:
            req = SolveRequest(
                problem=p, lane=eb.LaneSpec(), resume_token=token
            )
            res, health, info = b2.submit(req).result(120)
            assert health is None
            assert info["resumed_from"] >= 1
            assert b2.metrics.snapshot()["resumed_total"] == 1
        finally:
            b2.close()
        assert _bitwise(res.u_cur, control.u_cur)
        assert _bitwise(res.u_prev, control.u_prev)
        assert _bitwise(res.abs_errors, control.abs_errors)

    def test_worker_crash_resumes_march_zero_client_errors(self, eng):
        p = self._long()
        control = self._control(eng, p)[0]
        plan = faults.parse_serve_spec(
            f"serve-chunk-crash:timesteps={p.timesteps},count=1"
        )
        b = self._batcher(eng, plan=plan)
        try:
            # the crash escapes the worker mid-march; the supervisor
            # restarts it and the item resumes from its in-memory
            # progress - the CLIENT never sees an error
            res, health, info = b.submit(_req(p)).result(120)
            assert health is None
            assert res.final_step == p.timesteps
            snap = b.metrics.snapshot()
            assert snap["worker_restarts_total"] == 1
            assert snap["resumed_total"] == 1
        finally:
            b.close()
        assert _bitwise(res.u_cur, control.u_cur)
        assert _bitwise(res.abs_errors, control.abs_errors)

    def test_drain_checkpoints_and_successor_resumes_bitwise(
        self, eng, tmp_path
    ):
        p = self._long()
        control = self._control(eng, p)[0]
        state_dir = str(tmp_path / "state")
        store = SolveStateStore(state_dir)
        plan = faults.parse_serve_spec(
            f"serve-slow-batch:seconds=0.4,timesteps={p.timesteps}"
        )
        b = self._batcher(eng, store=store, plan=plan)
        fut = b.submit(_req(p))
        # wait until the march is genuinely in flight, then drain
        deadline = time.monotonic() + 60.0
        while (b.metrics.snapshot()["chunks_total"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.005)
        assert b.metrics.snapshot()["chunks_total"] >= 1
        b.close(timeout=60.0, drain=True)
        with pytest.raises(PreemptedError) as ei:
            fut.result(0)
        token = ei.value.resume_token
        assert SolveStateStore.valid_token(token)
        # the "successor replica": a DIFFERENT engine sharing only the
        # state dir (the cross-replica handoff surface)
        eng2 = ServeEngine(bucket_sizes=(1,), interpret=True)
        b2 = self._batcher(eng2, store=SolveStateStore(state_dir))
        try:
            req = SolveRequest(
                problem=p, lane=eb.LaneSpec(), resume_token=token
            )
            res, health, info = b2.submit(req).result(120)
            assert health is None
            assert info["resumed_from"] >= 1
        finally:
            b2.close()
        assert _bitwise(res.u_cur, control.u_cur)
        assert _bitwise(res.u_prev, control.u_prev)
        assert _bitwise(res.abs_errors, control.abs_errors)

    def test_corrupt_token_422s_cleanly_breaker_never_hears(
        self, eng, tmp_path
    ):
        p = self._long()
        store = SolveStateStore(str(tmp_path / "state"))
        # mint a genuine token, then corrupt its bytes on disk
        # (serve-handoff-corrupt truncates it at load time)
        plan = faults.parse_serve_spec(
            f"serve-slow-batch:seconds=0.25,timesteps={p.timesteps}"
        )
        b = self._batcher(eng, store=store, plan=plan)
        try:
            fut = b.submit(_req(p), deadline=time.monotonic() + 0.4)
            with pytest.raises(DeadlineExceededError) as ei:
                fut.result(120)
            token = ei.value.resume_token
        finally:
            b.close()
        corrupt = faults.parse_serve_spec("serve-handoff-corrupt:count=1")
        b2 = self._batcher(eng, store=store, plan=corrupt)
        try:
            req = SolveRequest(
                problem=p, lane=eb.LaneSpec(), resume_token=token
            )
            with pytest.raises(InvalidStateTokenError,
                               match="content verification"):
                b2.submit(req).result(120)
            # an unknown (never-minted) token is the same clean 422
            req2 = SolveRequest(
                problem=p, lane=eb.LaneSpec(), resume_token="0" * 64
            )
            with pytest.raises(InvalidStateTokenError, match="not found"):
                b2.submit(req2).result(120)
        finally:
            b2.close()
        # neither rejection fed the engine's circuit breaker
        assert eng.breaker_stats()["open"] == 0

    def test_token_identity_mismatch_is_rejected(self, eng, tmp_path):
        store = SolveStateStore(str(tmp_path / "state"))
        p = self._long()
        plan = faults.parse_serve_spec(
            f"serve-slow-batch:seconds=0.25,timesteps={p.timesteps}"
        )
        b = self._batcher(eng, store=store, plan=plan)
        try:
            fut = b.submit(_req(p), deadline=time.monotonic() + 0.4)
            with pytest.raises(DeadlineExceededError) as ei:
                fut.result(120)
            token = ei.value.resume_token
        finally:
            b.close()
        # replaying the token against a DIFFERENT solve is a clean 422
        other = Problem(N=8, timesteps=13)
        b2 = self._batcher(eng, store=store)
        try:
            req = SolveRequest(
                problem=other, lane=eb.LaneSpec(), resume_token=token
            )
            with pytest.raises(InvalidStateTokenError,
                               match="does not match"):
                b2.submit(req).result(120)
        finally:
            b2.close()


class TestMetricsRegistryIntegration:
    """ServeMetrics writes through the registry: the JSON snapshot keeps
    its historical fields while the same cut renders as Prometheus text,
    and snapshot() holds ONE lock across everything it reads."""

    def test_snapshot_fields_preserved_and_extended(self):
        m = ServeMetrics()
        m.observe_request()
        m.observe_response(True)
        m.observe_batch(occupancy=3, cells=1e9,
                        solve_seconds=0.5, batch_size=4)
        m.observe_latency(0.1)
        snap = m.snapshot()
        # historical fields, exact names and derivations
        assert snap["requests_total"] == 1
        assert snap["responses_ok"] == 1
        assert snap["responses_error"] == 0
        assert snap["batches_total"] == 1
        assert snap["batch_occupancy_mean"] == 3.0
        assert snap["batch_occupancy_max"] == 3
        # no `masked` passed (a chunked solo march): no lane march
        assert snap["masked_batches_total"] == 0
        assert snap["unmasked_batches_total"] == 0
        assert snap["latency_p50_ms"] == 100.0
        assert snap["aggregate_gcells_per_s"] == 2.0
        # new observability fields
        assert snap["queue_depth"] == 0
        assert snap["rejected_total"] == 0
        assert snap["padding_lanes_total"] == 1
        assert snap["last_batch_age_seconds"] is not None

    def test_last_batch_age_none_only_before_any_batch(self):
        """The /healthz discriminator: age is None IFF no batch was
        ever executed.  Keyed on the batches counter, not the timestamp
        gauge, so a gauge sitting at its 0.0 default ("idle since t=0")
        can never read as "never executed"."""
        m = ServeMetrics()
        assert m.last_batch_age() is None
        m.observe_batch(occupancy=1, cells=1.0,
                        solve_seconds=0.1)
        assert m.last_batch_age() is not None
        # even a zero timestamp is "has executed", not "never"
        m._last_batch_ts.set(0.0)
        assert m.last_batch_age() is not None

    def test_json_and_text_views_agree(self):
        m = ServeMetrics()
        for _ in range(3):
            m.observe_request()
        m.observe_response(True)
        m.observe_response(False)
        m.observe_batch(occupancy=2, cells=2e9,
                        solve_seconds=1.0, batch_size=2)
        m.observe_latency(0.2)
        snap = m.snapshot()
        samples, types = parse_prometheus(m.registry.render_prometheus())
        assert types["wavetpu_serve_requests_total"] == "counter"
        assert samples["wavetpu_serve_requests_total"] == \
            snap["requests_total"] == 3
        assert samples['wavetpu_serve_responses_total{status="ok"}'] == \
            snap["responses_ok"] == 1
        assert samples['wavetpu_serve_responses_total{status="error"}'] \
            == snap["responses_error"] == 1
        assert samples["wavetpu_serve_batches_total"] == \
            snap["batches_total"] == 1
        assert samples["wavetpu_serve_batch_occupancy_max"] == \
            snap["batch_occupancy_max"] == 2
        # histogram triplet for the latency distribution
        assert samples["wavetpu_serve_request_seconds_count"] == 1
        assert samples["wavetpu_serve_request_seconds_sum"] == \
            pytest.approx(0.2)
        assert samples['wavetpu_serve_request_seconds_bucket{le="+Inf"}'] \
            == 1


# ---- request parsing ----

class TestParse:
    def test_minimal_request(self):
        req = parse_solve_request({"N": 8}, default_kernel="roll")
        assert req.problem.N == 8
        assert req.path == "roll"
        assert req.k == 1

    def test_fuse_steps_selects_kfused(self):
        req = parse_solve_request(
            {"N": 8, "fuse_steps": 2, "kernel": "pallas"},
            default_kernel="roll",
        )
        assert req.path == "kfused" and req.k == 2

    def test_fuse_steps_rejects_roll(self):
        with pytest.raises(ValueError, match="pallas"):
            parse_solve_request(
                {"N": 8, "fuse_steps": 2, "kernel": "roll"},
                default_kernel="roll",
            )

    def test_pi_lengths_and_preset_fields(self):
        req = parse_solve_request(
            {"N": 8, "Lx": "pi", "c2_field": "gaussian-lens"},
            default_kernel="roll",
        )
        assert req.problem.Lx == pytest.approx(np.pi)
        assert req.lane.c2tau2_field is not None

    def test_bad_fields_rejected(self):
        for body, msg in [
            ({}, "missing required field N"),
            ({"N": 8, "scheme": "x"}, "scheme"),
            ({"N": 8, "dtype": "f16"}, "dtype"),
            ({"N": 8, "c2_field": "nope"}, "c2_field"),
            ({"N": 8, "steps": 99}, "stop_step"),
            ({"N": 8, "scheme": "compensated", "c2_field": "constant"},
             "c2_field"),
            ({"N": 8, "phase": 1.0, "c2_field": "constant"},
             "analytic layer-1"),
            ({"N": 8, "mesh": [2, 2]}, "mesh"),
            ({"N": 8, "mesh": [99, 99, 99]}, "devices"),
            ({"N": 8, "mesh": [2, 1, 1], "scheme": "compensated"},
             "standard scheme"),
            ({"N": 8, "mesh": [2, 1, 1], "fuse_steps": 2,
              "kernel": "pallas"}, "fuse_steps"),
            ({"N": 8, "mesh": [2, 1, 1], "c2_field": "constant"},
             "c2_field"),
        ]:
            with pytest.raises(ValueError, match=msg):
                parse_solve_request(body, default_kernel="roll")

    def test_compensated_bf16_rejected_at_parse(self):
        with pytest.raises(ValueError, match="f32/f64"):
            parse_solve_request(
                {"N": 8, "scheme": "compensated", "dtype": "bf16"},
                default_kernel="roll",
            )

    def test_compensated_shifted_phase_now_parses(self):
        # The vmapped compensated core serves shifted phases; the old
        # parse-time refusal is gone.
        req = parse_solve_request(
            {"N": 8, "scheme": "compensated", "phase": 1.0},
            default_kernel="roll",
        )
        assert req.scheme == "compensated"
        assert req.lane.phase == 1.0

    def test_mesh_request_parses(self):
        req = parse_solve_request(
            {"N": 8, "mesh": [2, 2, 1], "phase": 1.0},
            default_kernel="roll",
        )
        assert req.mesh_shape == (2, 2, 1)
        assert req.path == "roll"


# ---- HTTP end to end ----

@pytest.fixture()
def server():
    httpd, state = build_server(
        port=0, max_wait=0.5, default_kernel="roll", interpret=True
    )
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield base, state
    httpd.shutdown()
    state.batcher.close()
    httpd.server_close()


def _post(base, body, timeout=120):
    code, payload, _headers = _post_full(base, body, timeout=timeout)
    return code, payload


def _post_full(base, body, timeout=120, headers=None):
    req = urllib.request.Request(
        base + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _get(base, path):
    with urllib.request.urlopen(base + path, timeout=30) as r:
        return r.status, json.loads(r.read())


class TestHTTP:
    def test_concurrent_requests_coalesce_with_own_reports(self, server):
        base, state = server
        results = [None] * 4
        phases = [6.283, 1.0, 0.5, 0.25]

        def worker(i):
            results[i] = _post(
                base, {"N": 8, "timesteps": 4, "phase": phases[i]}
            )

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        errs = set()
        for code, body in results:
            assert code == 200
            assert body["status"] == "ok"
            assert body["batch"]["occupancy"] > 1
            assert body["report"]["final_step"] == 4
            assert len(body["report"]["abs_errors"]) == 5
            assert "grids initialized in" in body["report_text"]
            errs.add(body["report"]["max_abs_error"])
        # four distinct phases -> four distinct per-request reports
        assert len(errs) == 4
        code, metrics = _get(base, "/metrics")
        assert code == 200
        assert metrics["batch_occupancy_max"] > 1
        assert metrics["requests_total"] == 4
        assert metrics["responses_ok"] == 4
        assert metrics["aggregate_gcells_per_s"] is not None
        assert metrics["latency_p50_ms"] is not None
        assert metrics["program_cache"]["programs"] >= 1

    def test_healthz(self, server):
        base, _ = server
        code, body = _get(base, "/healthz")
        assert code == 200
        assert body["status"] == "ok"

    def test_healthz_memory_fields(self, server):
        """Device-memory visibility: both fields present and unit-pinned
        in the name (`_bytes`); None exactly when the backend has no
        memory_stats() (the CPU backend CI runs on), else non-negative
        ints."""
        base, _ = server
        code, body = _get(base, "/healthz")
        assert code == 200
        assert "memory_bytes_in_use" in body
        assert "memory_peak_bytes" in body
        for field in ("memory_bytes_in_use", "memory_peak_bytes"):
            v = body[field]
            assert v is None or (isinstance(v, int) and v >= 0)
        # Both sides of the contract agree: None iff the probe says
        # unsupported.
        from wavetpu.obs import perf

        snap = perf.memory_snapshot()
        assert (body["memory_bytes_in_use"] is None) == (snap is None)

    def test_healthz_liveness_vs_readiness(self, server):
        """The readiness split: `status: ok` = the process serves HTTP;
        `ready` = route traffic here - false while the warmup compile
        runs or once draining is set, so a load balancer pulls the
        replica BEFORE drain starts failing requests.  The loadgen
        preflight refuses a not-ready target the same way."""
        from wavetpu.loadgen import runner as lg_runner

        base, state = server
        code, body = _get(base, "/healthz")
        assert code == 200
        assert body["ready"] is True and body["warming"] is False
        state.warming = True
        try:
            code, body = _get(base, "/healthz")
            assert body["status"] == "ok"  # alive...
            assert body["ready"] is False  # ...but do not route yet
            with pytest.raises(lg_runner.PreflightError,
                               match="not ready"):
                lg_runner.preflight(base)
        finally:
            state.warming = False
        state.draining = True
        try:
            code, body = _get(base, "/healthz")
            assert body["ready"] is False and body["draining"] is True
        finally:
            state.draining = False
        assert _get(base, "/healthz")[1]["ready"] is True

    def test_429_and_503_carry_retry_after(self):
        httpd, state = build_server(
            port=0, max_wait=0.1, default_kernel="roll",
            interpret=True, max_queue=0,
        )
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            code, body, headers = _post_full(
                base, {"N": 8, "timesteps": 4}
            )
            assert code == 429
            assert headers.get("Retry-After") is not None
            assert body["retriable"] is True
            state.draining = True
            code, body, headers = _post_full(
                base, {"N": 8, "timesteps": 4}
            )
            assert code == 503
            assert headers.get("Retry-After") is not None
            assert body["retriable"] is True
        finally:
            state.draining = False
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()

    def test_metrics_json_carries_breaker_block(self, server):
        base, _ = server
        code, snap = _get(base, "/metrics")
        assert code == 200
        assert snap["breaker"]["enabled"] is True
        assert snap["breaker"]["open"] == 0

    def test_healthz_idle_vs_wedged_fields(self, server):
        # The load-balancer discriminator fields: uptime, draining, and
        # last-batch age (null while idle, a number after traffic).
        base, state = server
        code, body = _get(base, "/healthz")
        assert code == 200
        assert body["uptime_seconds"] >= 0
        assert body["draining"] is False
        assert body["last_batch_age_seconds"] is None
        _post(base, {"N": 8, "timesteps": 4})
        code, body = _get(base, "/healthz")
        assert body["last_batch_age_seconds"] is not None
        assert body["last_batch_age_seconds"] >= 0
        state.draining = True
        try:
            code, body = _get(base, "/healthz")
            assert body["draining"] is True
        finally:
            state.draining = False

    def test_metrics_prometheus_text_negotiated(self, server):
        base, state = server
        _post(base, {"N": 8, "timesteps": 4})
        req = urllib.request.Request(
            base + "/metrics", headers={"Accept": "text/plain"}
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        samples, types = parse_prometheus(text)
        assert samples["wavetpu_serve_requests_total"] >= 1
        assert types["wavetpu_serve_request_seconds"] == "histogram"
        assert samples["wavetpu_serve_request_seconds_count"] >= 1
        # engine metrics share the server registry (build_server wiring)
        assert samples['wavetpu_program_cache_events_total{event="miss"}'] \
            >= 1
        # the same cut agrees with the JSON view
        code, snap = _get(base, "/metrics")
        assert code == 200
        assert snap["requests_total"] == \
            samples["wavetpu_serve_requests_total"]
        # default Accept still gets the historical JSON shape
        assert "program_cache" in snap

    def test_request_and_batch_spans_join_on_request_id(
        self, server, tmp_path
    ):
        from wavetpu.obs import report as obs_report
        from wavetpu.obs import tracing

        base, _ = server
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            code, body = _post(base, {"N": 8, "timesteps": 4})
            assert code == 200
        finally:
            tracing.disable()
        recs = [json.loads(line) for line in open(path)]
        reqs = [r for r in recs if r["kind"] == "serve.request"]
        batches = [r for r in recs if r["kind"] == "serve.batch"]
        assert len(reqs) == 1 and len(batches) == 1
        rid = reqs[0]["attrs"]["request_id"]
        assert rid in batches[0]["attrs"]["request_ids"]
        assert reqs[0]["attrs"]["status"] == 200
        assert batches[0]["attrs"]["padding_lanes"] == 0
        # execute (and on first contact compile) spans nest under batch
        execs = [r for r in recs if r["kind"] == "serve.execute"]
        assert execs and execs[0]["parent_id"] == batches[0]["span_id"]
        # trace-report stitches the critical path from the id
        view = obs_report.request_view(recs, rid)
        kinds = {r["kind"] for r in view}
        assert {"serve.request", "serve.batch", "serve.execute"} <= kinds

    def test_server_timing_components_sum_to_total(self, server):
        """Acceptance: every /solve response carries Server-Timing whose
        additive components (queue + compile + execute) sum to within
        10% of the server-measured wall (`total`), and the per-request
        timing rides the JSON batch context too."""
        from wavetpu.loadgen.runner import parse_server_timing

        base, _ = server
        for i in range(2):  # first contact (cold compile) AND warm
            t0 = time.monotonic()
            code, body, headers = _post_full(
                base, {"N": 8, "timesteps": 4, "phase": 1.0 + i}
            )
            client_wall = time.monotonic() - t0
            assert code == 200
            timing = parse_server_timing(headers.get("Server-Timing"))
            assert set(timing) == {
                "queue", "compile", "execute", "padding", "total"
            }
            additive = timing["queue"] + timing["compile"] + \
                timing["execute"]
            # components ~= the server-measured wall (parse/serialize
            # overhead is the slack; 10% + a tiny absolute epsilon for
            # the CI-scale solves where total is single-digit ms)
            assert abs(additive - timing["total"]) <= \
                0.1 * timing["total"] + 0.010
            # server total never exceeds what the client measured
            assert timing["total"] <= client_wall + 0.010
            # padding is a subset-of-execute attribution
            assert timing["padding"] <= timing["execute"] + 1e-9
            # and the same attribution is in the JSON batch context
            jt = body["batch"]["timing"]
            assert jt["compile_s"] == pytest.approx(
                timing["compile"], abs=1e-4
            )
        # the cold/warm split is visible: first request compiled,
        # second hit the cache
        assert body["batch"]["warm"] == "true"

    def test_request_id_echoed_and_client_id_wins(self, server):
        base, _ = server
        # client-minted id is echoed verbatim
        code, _body, headers = _post_full(
            base, {"N": 8, "timesteps": 4},
            headers={"X-Request-Id": "lg-abc-7"},
        )
        assert code == 200
        assert headers.get("X-Request-Id") == "lg-abc-7"
        # junk ids (bad chars / over-long) are dropped, not reflected
        junk = 'evil"id with spaces' + "x" * 80
        code, _body, headers = _post_full(
            base, {"N": 8, "timesteps": 4},
            headers={"X-Request-Id": junk},
        )
        assert code == 200
        assert headers.get("X-Request-Id") != junk

    def test_client_request_id_tags_server_spans(self, server, tmp_path):
        """The loadgen join contract: a client-supplied X-Request-Id is
        THE request_id on the server's trace spans, so a report outlier
        resolves via `wavetpu trace-report --request ID`."""
        from wavetpu.obs import report as obs_report
        from wavetpu.obs import tracing

        base, _ = server
        path = str(tmp_path / "trace.jsonl")
        tracing.configure(path)
        try:
            code, _, headers = _post_full(
                base, {"N": 8, "timesteps": 4},
                headers={"X-Request-Id": "lg-join-1"},
            )
            assert code == 200
        finally:
            tracing.disable()
        recs = [json.loads(line) for line in open(path)]
        view = obs_report.request_view(recs, "lg-join-1")
        kinds = {r["kind"] for r in view}
        assert {"serve.request", "serve.batch", "serve.execute"} <= kinds

    def test_metrics_openmetrics_exemplars_negotiated(self, server):
        base, _ = server
        _post_full(base, {"N": 8, "timesteps": 4},
                   headers={"X-Request-Id": "lg-ex-1"})
        req = urllib.request.Request(
            base + "/metrics",
            headers={"Accept": "application/openmetrics-text"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith(
                "application/openmetrics-text"
            )
            text = r.read().decode()
        samples, _types, exemplars = parse_prometheus(
            text, with_exemplars=True
        )
        assert text.rstrip().endswith("# EOF")
        # the latency histogram carries the request id as an exemplar
        latency_ex = [
            ex for name, ex in exemplars.items()
            if name.startswith("wavetpu_serve_request_seconds_bucket")
        ]
        assert any(
            ex["labels"].get("request_id") == "lg-ex-1"
            for ex in latency_ex
        )
        # plain text/plain stays exemplar-free (0.0.4 parsers)
        req = urllib.request.Request(
            base + "/metrics", headers={"Accept": "text/plain"}
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            plain = r.read().decode()
        assert " # " not in plain and "# EOF" not in plain

    def test_malformed_content_length_gets_400(self, server):
        """A junk Content-Length header must produce a 400 JSON error,
        not an unhandled handler exception (dropped connection)."""
        import socket

        base, _ = server
        host, port = base.replace("http://", "").split(":")
        with socket.create_connection((host, int(port)), timeout=30) as s:
            s.sendall(
                b"POST /solve HTTP/1.1\r\nHost: x\r\n"
                b"Content-Length: abc\r\n\r\n"
            )
            data = s.recv(65536)
        status_line = data.split(b"\r\n", 1)[0]
        assert b" 400 " in status_line + b" "
        assert b"Content-Length" in data
        # A NEGATIVE length must 400 too - rfile.read(-1) would block
        # to EOF and pin the handler thread forever (thread-exhaustion
        # DoS), so it is the same malformed-header case.
        with socket.create_connection((host, int(port)), timeout=30) as s:
            s.sendall(
                b"POST /solve HTTP/1.1\r\nHost: x\r\n"
                b"Content-Length: -1\r\n\r\n"
            )
            data = s.recv(65536)
        assert b" 400 " in data.split(b"\r\n", 1)[0] + b" "

    def test_max_body_bytes_413(self):
        httpd, state = build_server(
            port=0, max_wait=0.1, default_kernel="roll",
            interpret=True, max_body_bytes=64,
        )
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            big = {"N": 8, "timesteps": 4, "pad": "x" * 500}
            code, body, _ = _post_full(base, big)
            assert code == 413
            assert "max-body-bytes" in body["error"]
            code, snap = _get(base, "/metrics")
            assert snap["limit_rejected_total"] == 1
            # and in the Prometheus view, labeled by limit
            samples, _ = parse_prometheus(
                state.metrics.registry.render_prometheus()
            )
            assert samples[
                'wavetpu_serve_limit_rejected_total{limit="body_bytes"}'
            ] == 1
            # a small request still serves
            code, _, _ = _post_full(base, {"N": 8, "timesteps": 4})
            assert code == 200
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()

    def test_max_lane_cells_422_before_scheduling(self):
        httpd, state = build_server(
            port=0, max_wait=0.1, default_kernel="roll",
            interpret=True, max_lane_cells=1000,  # (N+1)^3 <= 1000
        )
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            code, body, _ = _post_full(base, {"N": 16, "timesteps": 4})
            assert code == 422
            assert "max-lane-cells" in body["error"]
            code, snap = _get(base, "/metrics")
            assert snap["limit_rejected_total"] == 1
            # nothing reached the scheduler
            assert snap["batches_total"] == 0
            code, _, _ = _post_full(base, {"N": 8, "timesteps": 4})
            assert code == 200  # 9^3 = 729 <= 1000
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()

    def test_queue_full_returns_429(self):
        httpd, state = build_server(
            port=0, max_wait=0.1, default_kernel="roll",
            interpret=True, max_queue=0,
        )
        thread = threading.Thread(target=httpd.serve_forever, daemon=True)
        thread.start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        try:
            code, body = _post(base, {"N": 8, "timesteps": 4})
            assert code == 429
            assert "queue full" in body["error"]
            code, snap = _get(base, "/metrics")
            assert snap["rejected_total"] == 1
            assert snap["responses_error"] == 1
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()

    def test_draining_returns_503(self, server):
        base, state = server
        state.draining = True
        try:
            code, body = _post(base, {"N": 8, "timesteps": 4})
            assert code == 503
            assert "draining" in body["error"]
        finally:
            state.draining = False

    def test_mesh_request_serves_sharded_batched(self, server):
        base, _ = server
        code, body = _post(
            base, {"N": 8, "timesteps": 4, "mesh": [2, 2, 1],
                   "phase": 1.0}, timeout=300,
        )
        assert code == 200
        assert "sharded(2, 2, 1)" in body["batch"]["path"]
        assert set(body["batch"]) >= {"occupancy", "batch_size", "path"}
        assert body["report"]["final_step"] == 4

    def test_bad_request_400(self, server):
        base, _ = server
        code, body = _post(base, {"timesteps": 4})
        assert code == 400
        assert "N" in body["error"]

    def test_unknown_route_404(self, server):
        base, _ = server
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/nope", timeout=30)
        assert ei.value.code == 404

    def test_watchdog_poisoned_request_422_batchmate_ok(self, server):
        base, _ = server
        results = [None] * 2
        bodies = [
            {"N": 8, "T": 26.0, "timesteps": 60, "c2_field": "constant"},
            {"N": 8, "T": 26.0, "timesteps": 60, "c2_field": "two-layer"},
        ]

        def worker(i):
            results[i] = _post(base, bodies[i], timeout=300)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(2)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        codes = sorted(r[0] for r in results)
        assert codes == [200, 422]
        bad = next(b for c, b in results if c == 422)
        assert "amax" in bad["error"]
        ok = next(b for c, b in results if c == 200)
        # a field request serves without the analytic oracle
        assert ok["report"]["errors_computed"] is False
        assert ok["report"]["max_abs_error"] is None


# ---- CLI entry points ----

class TestPreemptibleHTTP:
    """The HTTP face of the preemption drill: 504-with-token, token
    resume with full error-history parity, token hygiene (400/422),
    and the tenant label riding serve-side metrics."""

    def _server(self, tmp_path, **kw):
        kw.setdefault("max_wait", 0.05)
        kw.setdefault("default_kernel", "roll")
        kw.setdefault("interpret", True)
        kw.setdefault("chunk_threshold", 64)
        kw.setdefault("chunk_steps", 1)
        kw.setdefault("solve_state_dir", str(tmp_path / "state"))
        httpd, state = build_server(port=0, **kw)
        threading.Thread(
            target=httpd.serve_forever, daemon=True
        ).start()
        return httpd, state, f"http://127.0.0.1:{httpd.server_address[1]}"

    def test_deadline_504_with_token_then_resume_matches(
        self, tmp_path
    ):
        httpd, state, base = self._server(tmp_path)
        body = {"N": 8, "timesteps": 193}
        try:
            # control march (also warms every chunk program, so the
            # deadline below expires mid-MARCH, not mid-compile)
            code, control = _post(base, body)
            assert code == 200
            assert control["batch"]["chunked"] is True
            # a budget far smaller than the march: 504 whose body
            # carries the resumable state token
            code, payload = _post(base, dict(body, deadline_ms=20))
            assert code == 504, payload
            token = payload.get("resume_token")
            assert SolveStateStore.valid_token(token), payload
            # resubmit with the token, no budget: the march finishes
            # and the FULL per-layer error history matches the
            # uninterrupted control exactly
            code, resumed = _post(base, dict(body, resume_token=token))
            assert code == 200, resumed
            assert resumed["report"]["final_step"] == 193
            assert resumed["batch"]["resumed_from"] >= 1
            assert (resumed["report"]["abs_errors"]
                    == control["report"]["abs_errors"])
            assert (resumed["report"]["rel_errors"]
                    == control["report"]["rel_errors"])
            _, metrics = _get(base, "/metrics")
            assert metrics["chunks_total"] > 0
            assert metrics["preempted_total"] >= 1
            assert metrics["resumed_total"] >= 1
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()

    def test_token_hygiene_400_and_422(self, tmp_path):
        httpd, state, base = self._server(tmp_path)
        body = {"N": 8, "timesteps": 193}
        try:
            # not even token-shaped: rejected at parse (400)
            code, payload = _post(base, dict(body, resume_token="zz"))
            assert code == 400
            # well-formed but never minted: clean 422, never retriable
            code, payload = _post(
                base, dict(body, resume_token="0" * 64)
            )
            assert code == 422
            assert "not found" in payload["error"]
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()

    def test_tenant_header_lands_in_metrics(self, tmp_path):
        httpd, state, base = self._server(tmp_path)
        try:
            code, _, _ = _post_full(
                base, {"N": 8, "timesteps": 3},
                headers={"X-Wavetpu-Tenant": "acme"},
            )
            assert code == 200
            req = urllib.request.Request(
                base + "/metrics", headers={"Accept": "text/plain"}
            )
            with urllib.request.urlopen(req, timeout=30) as r:
                text = r.read().decode()
            samples, _types = parse_prometheus(text)
            assert samples[
                'wavetpu_serve_tenant_requests_total{tenant="acme"}'
            ] == 1.0
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()


class TestDistributedTracingServe:
    """The replica's half of the fleet trace contract
    (docs/observability.md "Distributed tracing"): traceparent echoed
    on every /solve answer, inbound context adopted as the remote
    parent of serve.request, the in-flight chunk-march gauge, and the
    originating trace context riding the resume checkpoint so a
    preempted march resumed under a NEW trace links back to its first
    request."""

    @staticmethod
    def _lower(headers):
        return {k.lower(): v for k, v in headers.items()}

    def test_untraced_replica_reflects_inbound_verbatim(self, server):
        base, _state = server
        tp = "00-" + "ab" * 16 + "-" + "12" * 8 + "-01"
        code, _body, hdrs = _post_full(
            base, {"N": 8, "timesteps": 3}, headers={"traceparent": tp}
        )
        assert code == 200
        # untraced tier: the join handle still answers - the inbound
        # header comes back untouched
        assert self._lower(hdrs).get("traceparent") == tp

    def test_untraced_replica_without_inbound_sends_no_header(
        self, server
    ):
        base, _state = server
        code, _body, hdrs = _post_full(base, {"N": 8, "timesteps": 3})
        assert code == 200
        assert "traceparent" not in self._lower(hdrs)

    def test_untraced_replica_drops_malformed_inbound(self, server):
        base, _state = server
        code, _body, hdrs = _post_full(
            base, {"N": 8, "timesteps": 3},
            headers={"traceparent": "00-nothex-11-01"},
        )
        assert code == 200
        assert "traceparent" not in self._lower(hdrs)

    def test_traced_replica_adopts_inbound_and_echoes_own_context(
        self, tmp_path
    ):
        from wavetpu.obs import tracing
        trace_path = str(tmp_path / "trace.jsonl")
        tracing.configure(trace_path)
        httpd, state = build_server(
            port=0, max_wait=0.05, default_kernel="roll", interpret=True
        )
        threading.Thread(
            target=httpd.serve_forever, daemon=True
        ).start()
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        tid, wire = "ab" * 16, "12" * 8
        try:
            code, _body, hdrs = _post_full(
                base, {"N": 8, "timesteps": 3},
                headers={"traceparent": f"00-{tid}-{wire}-01"},
            )
            assert code == 200
            echoed = tracing.parse_traceparent(
                self._lower(hdrs)["traceparent"]
            )
            # traced tier overwrites the echo with its OWN context:
            # same fleet trace id, fresh wire span id
            assert echoed is not None
            assert echoed[0] == tid
            assert echoed[1] != wire
            # no inbound context: a fresh trace id is minted
            code, _body, hdrs2 = _post_full(
                base, {"N": 8, "timesteps": 3}
            )
            assert code == 200
            fresh = tracing.parse_traceparent(
                self._lower(hdrs2)["traceparent"]
            )
            assert fresh is not None and fresh[0] != tid
        finally:
            httpd.shutdown()
            state.batcher.close()
            httpd.server_close()
            tracing.disable()
        recs = [json.loads(l) for l in open(trace_path)]
        adopted = [
            r for r in recs
            if r.get("kind") == "serve.request"
            and r.get("trace_id") == tid
        ]
        assert len(adopted) == 1
        # the inbound wire id IS the remote parent, and the span
        # advertises the echoed wire id for the cross-process joiner
        assert adopted[0]["parent_id"] == wire
        assert adopted[0]["attrs"]["w3c_id"] == echoed[1]

    def test_inflight_gauge_and_origin_trace_ride_checkpoint(
        self, tmp_path
    ):
        from wavetpu.obs import tracing
        eng = ServeEngine(bucket_sizes=(1,), interpret=True)
        p = Problem(N=8, timesteps=17)
        store_dir = str(tmp_path / "state")
        plan = faults.parse_serve_spec(
            f"serve-slow-batch:seconds=0.25,timesteps={p.timesteps}"
        )
        origin = ("ab" * 16, "cd" * 8)
        b = DynamicBatcher(
            eng, max_wait=0.02, fault_plan=plan, chunk_threshold=8,
            chunk_steps=4, state_store=SolveStateStore(store_dir),
        )
        gauge = b.metrics._inflight_chunks
        # Compile the chunk programs first: a cold compile takes about
        # as long as the deadline, which would then expire before the
        # first chunk and leave the gauge up for only a few ms.
        req = _req(p)
        eng.chunk_runner(
            p, req.scheme, req.path, req.k, req.dtype_name, chunk_steps=4
        )
        try:
            fut = b.submit(
                req, deadline=time.monotonic() + 0.4,
                trace_context=origin,
            )
            # the gauge rises while the march is genuinely in flight...
            seen, deadline = 0.0, time.monotonic() + 60.0
            while time.monotonic() < deadline and not fut.done():
                seen = max(seen, gauge.value())
                time.sleep(0.005)
            with pytest.raises(DeadlineExceededError) as ei:
                fut.result(120)
            token = ei.value.resume_token
        finally:
            b.close()
        assert seen == 1.0
        # ...and falls back to zero however the march ends (here:
        # deadline preemption)
        assert gauge.value() == 0.0
        # resume on a traced successor under a DIFFERENT client trace:
        # the checkpoint's origin_trace turns into span links, so the
        # whole march is still one joinable story
        trace_path = str(tmp_path / "trace.jsonl")
        tracing.configure(trace_path)
        b2 = DynamicBatcher(
            eng, max_wait=0.02, chunk_threshold=8, chunk_steps=4,
            state_store=SolveStateStore(store_dir),
        )
        fresh = ("12" * 16, "34" * 8)
        try:
            req = SolveRequest(
                problem=p, lane=eb.LaneSpec(), resume_token=token
            )
            res, health, info = b2.submit(
                req, trace_context=fresh
            ).result(120)
            assert health is None
            assert info["resumed_from"] >= 1
        finally:
            b2.close()
            tracing.disable()
        end = time.monotonic() + 5.0
        while (b2.metrics._inflight_chunks.value() != 0.0
               and time.monotonic() < end):
            time.sleep(0.005)
        assert b2.metrics._inflight_chunks.value() == 0.0
        recs = [json.loads(l) for l in open(trace_path)]
        chunks = [r for r in recs if r.get("kind") == "serve.chunk"]
        assert chunks
        for r in chunks:
            assert r.get("trace_id") == fresh[0]
            assert r.get("links") == [
                {"trace_id": origin[0], "span_id": origin[1]}
            ]


class TestCLI:
    def test_wavetpu_version(self, capsys):
        from wavetpu import __version__
        from wavetpu.cli import main

        assert main(["--version"]) == 0
        assert __version__ in capsys.readouterr().out

    def test_wavetpu_serve_version(self, capsys):
        from wavetpu import __version__
        from wavetpu.cli import main

        assert main(["serve", "--version"]) == 0
        out = capsys.readouterr().out
        assert "wavetpu-serve" in out and __version__ in out

    def test_serve_rejects_unknown_flag(self, capsys):
        from wavetpu.cli import main

        assert main(["serve", "--frobnicate", "1"]) == 2

    def test_serve_rejects_malformed_warmup(self, capsys):
        """Malformed --warmup values are usage errors (exit 2 with the
        usage line, like every other numeric flag), not tracebacks."""
        from wavetpu.serve.api import main

        assert main(["--warmup", "8x4"]) == 2
        assert "usage" in capsys.readouterr().err
        assert main(["--warmup", "8,4,2,9"]) == 2
        assert "--warmup wants" in capsys.readouterr().err

    def test_serve_main_crash_stops_telemetry(self, tmp_path,
                                              monkeypatch, capsys):
        """A crash after telemetry start but before/at serve (an
        accept-loop failure injected here; --warmup now compiles in the
        background and records failures instead of crashing main) must
        not leak the heartbeat daemon or leave the process tracer bound
        for an in-process caller."""
        from http.server import ThreadingHTTPServer

        from wavetpu.obs import tracing
        from wavetpu.serve.api import main

        def boom(self, *a, **kw):
            raise RuntimeError("injected accept-loop failure")

        monkeypatch.setattr(ThreadingHTTPServer, "serve_forever", boom)
        with pytest.raises(RuntimeError, match="injected"):
            main([
                "--port", "0", "--kernel", "roll",
                "--telemetry-dir", str(tmp_path / "tel"),
            ])
        assert not tracing.enabled()
        # the final heartbeat landed on the way out
        assert (tmp_path / "tel" / "heartbeat.jsonl").exists()

    def test_serve_rejects_malformed_breaker_flags(self, capsys):
        from wavetpu.serve.api import main

        assert main(["--breaker-threshold", "x"]) == 2
        assert main(["--breaker-cooldown-s", "y"]) == 2

    def test_program_key_shape(self):
        p = Problem(N=8, timesteps=3)
        key = ProgramKey.for_batch(
            p, "standard", "roll", 4, "f32", False, True, 2
        )
        assert key.k == 1  # non-kfused paths normalize k
        assert key.batch == 2
        assert key.mesh is None  # single-device default
        sharded_key = ProgramKey.for_batch(
            p, "standard", "roll", 4, "f32", False, True, 2, (2, 2, 1)
        )
        assert sharded_key.mesh == (2, 2, 1)
        assert sharded_key != key
