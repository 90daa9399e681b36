"""Multi-tenant QoS: priority classes, per-tenant quotas, brownout.

The acceptance pins for the QoS layer (ISSUE: priority-class
scheduling, per-tenant quotas, adaptive overload shedding):

 * the class ladder is ONE ladder - quota.py's stdlib-only duplicate
   must stay identical to the scheduler's;
 * WDRR keeps an interactive flood from starving best_effort, and a
   single backlogged class pays zero QoS (plain FIFO);
 * a low-priority chunked march preempted per-chunk by interactive
   traffic finishes BITWISE identical to its unloaded run;
 * token buckets answer 429 with the MEASURED refill wait, and the
   retrying client honors exactly the value the server computed;
 * the brownout ladder escalates immediately and de-escalates one
   hysteresis-gated rung at a time, never shedding interactive;
 * replicas only trust tenant/priority headers carrying the router's
   --proxy-token (spoofs are counted and served untenanted);
 * the router clamps a tenant's self-claimed class to its ceiling and
   stamps the effective one downstream;
 * loadgen's tenants mix + per-tenant report/gate close the loop.
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
from wavetpu.fleet import quota
from wavetpu.loadgen import report as lg_report
from wavetpu.loadgen import runner, trace
from wavetpu.serve import scheduler as sched
from wavetpu.serve.api import build_server, format_retry_after
from wavetpu.serve.engine import ServeEngine
from wavetpu.serve.resilience import ShedError
from wavetpu.serve.scheduler import (
    BrownoutController,
    DynamicBatcher,
    ServeMetrics,
    SolveRequest,
)

from tests.test_obs import parse_prometheus


# ---- the one class ladder ----

class TestClassLadder:
    def test_quota_ladder_identical_to_scheduler_ladder(self):
        # quota.py duplicates the tuple (the router must not import the
        # jax-transitive serve package); this pin is the only thing
        # keeping the two from drifting.
        assert quota.PRIORITY_CLASSES == sched.PRIORITY_CLASSES
        assert quota.DEFAULT_PRIORITY == sched.DEFAULT_PRIORITY

    def test_normalize_is_lenient_never_raises(self):
        for fn in (quota.normalize_priority, sched.normalize_priority):
            assert fn(" Interactive ") == "interactive"
            assert fn("best_effort") == "best_effort"
            assert fn(None) == "batch"
            assert fn("turbo") == "batch"
            assert fn(7) == "batch"
            assert fn("junk", default="best_effort") == "best_effort"

    def test_clamp_demotes_never_promotes(self):
        assert quota.clamp_priority("interactive", "batch") == "batch"
        assert quota.clamp_priority("best_effort", "batch") \
            == "best_effort"
        assert quota.clamp_priority("batch", "interactive") == "batch"

    def test_effective_priority_default_then_ceiling(self):
        cfg = quota.TenantConfig(
            tenant="t", priority="batch", priority_ceiling="batch"
        )
        assert cfg.effective_priority(None) == "batch"
        # a self-promotion past the ceiling is clamped, not an error
        assert cfg.effective_priority("interactive") == "batch"
        assert cfg.effective_priority("best_effort") == "best_effort"
        assert cfg.effective_priority("junk") == "batch"

    def test_parse_tenant_entry_clamps_default_to_ceiling(self):
        cfg = quota.parse_tenant_entry("k", {
            "tenant": "t", "priority": "interactive",
            "priority_ceiling": "batch",
        })
        assert cfg.priority == "batch"
        assert cfg.priority_ceiling == "batch"


# ---- token buckets + pricing ----

class TestQuota:
    def test_bucket_starts_full_and_measures_refill(self):
        b = quota.TokenBucket(rate=10.0, burst=2.0)
        ok, retry = b.try_take(2.0)
        assert ok and retry == 0.0
        ok, retry = b.try_take(1.5)
        assert not ok
        # measured wait for 1.5 tokens at 10/s: ~0.15 s (minus the
        # sliver refilled since the first take)
        assert 0.05 < retry <= 0.15
        # the refused take left the bucket untouched
        assert b.tokens() < 0.1

    def test_bucket_refills_toward_burst_cap(self):
        b = quota.TokenBucket(rate=100.0, burst=5.0)
        b.try_take(5.0)
        time.sleep(0.12)
        assert b.tokens() == pytest.approx(5.0, abs=0.5)  # capped

    def test_price_cells_is_geometric_times_path_weight(self):
        assert quota.price_cells({"N": 8, "timesteps": 6}) \
            == pytest.approx(9 ** 3 * 6)
        # unparseable bodies price 0 (the replica 400s them anyway)
        assert quota.price_cells(None) == 0.0
        assert quota.price_cells({"N": "x"}) == 0.0
        assert quota.price_cells({"N": -4, "timesteps": 6}) == 0.0

    def test_admit_clamps_oversized_cost_to_one_full_bucket(self):
        # a request bigger than the burst pays one full refill instead
        # of being unreachable forever
        cfg = quota.TenantConfig(
            tenant="t", cells_per_s=10.0, cells_burst=10.0
        )
        qm = quota.QuotaManager()
        ok, _ = qm.admit(cfg, cells=50.0)
        assert ok  # full bucket covers the clamped cost
        ok, retry = qm.admit(cfg, cells=50.0)
        assert not ok
        assert 0.5 < retry <= 1.0  # ~10 tokens / 10 per s

    def test_cells_refusal_does_not_refund_the_rps_token(self):
        cfg = quota.TenantConfig(
            tenant="t", rps=100.0, burst=100.0,
            cells_per_s=10.0, cells_burst=10.0,
        )
        qm = quota.QuotaManager()
        assert qm.admit(cfg, cells=10.0)[0]
        assert not qm.admit(cfg, cells=10.0)[0]
        # two requests arrived -> two rps tokens spent, no refund for
        # the refused one (oversized floods must not probe for free)
        assert qm._rps["t"].tokens() == pytest.approx(98.0, abs=0.5)
        assert qm.rejected_per_tenant == {"t": 1}
        assert qm.snapshot()["quota_rejected_per_tenant"] == {"t": 1}

    def test_default_buckets_cover_passthrough_tenants(self):
        qm = quota.QuotaManager(default_rps=1000.0)
        assert qm.enforces_anything
        cfg = quota.TenantConfig(tenant="walkin")  # all-None limits
        assert qm.admit(cfg, cells=0.0)[0]
        assert "walkin" in qm._rps
        assert not quota.QuotaManager().enforces_anything


# ---- measured Retry-After, server + client sides ----

class TestRetryAfter:
    def test_format_rounds_up_to_at_least_one_second(self):
        assert format_retry_after(0.2) == "1"
        assert format_retry_after(1.4) == "1"
        assert format_retry_after(1.6) == "2"

    def test_metrics_fallback_when_no_drain_history(self):
        m = ServeMetrics()
        assert m.retry_after_s(5) == 1.0
        assert m.retry_after_s(5, fallback=3.5) == 3.5

    def test_client_honors_exactly_the_servers_computation(self):
        # the pin: server-side measured seconds -> wire header ->
        # client parse round-trips to the same honored wait
        from wavetpu.client import parse_retry_after
        m = ServeMetrics()
        wire = format_retry_after(m.retry_after_s(4, fallback=2.6))
        assert parse_retry_after({"Retry-After": wire}) == 3.0


# ---- WDRR scheduling ----

class _GateEngine:
    """max_batch=1 stub whose solve() blocks until released - each
    release exposes exactly one scheduler pick, so `order` IS the
    worker's pick sequence."""

    max_batch = 1

    def __init__(self):
        self.order = []
        self.entered = threading.Semaphore(0)
        self.release = threading.Semaphore(0)

    def solve(self, problem, lanes, scheme, path, k, dtype_name,
              mesh=None, timing=None):
        self.order.append(problem.timesteps)
        self.entered.release()
        self.release.acquire()
        if timing is not None:
            timing["compile_seconds"] = 0.0
            timing["warm"] = "true"
        results = [
            types.SimpleNamespace(steps_computed=problem.timesteps)
            for _ in lanes
        ]
        return types.SimpleNamespace(
            results=results, n_lanes=len(lanes), batch_size=len(lanes),
            path=path, masked=False,
            solve_seconds=0.0, aggregate_gcells_per_second=1.0,
        ), [None] * len(lanes)


def _qreq(timesteps, priority):
    # distinct timesteps -> distinct program keys, so nothing coalesces
    # and the engine-observed order is the raw pick order
    return SolveRequest(
        problem=Problem(N=8, timesteps=timesteps),
        lane=eb.LaneSpec(), priority=priority,
    )


def _drive(classes_by_timesteps):
    """Submit one request per (timesteps, class), with the worker held
    inside the FIRST solve so the rest stash as one backlog; release
    everything and return the engine's pick order as class names."""
    eng = _GateEngine()
    b = DynamicBatcher(eng, max_wait=0.001)
    mapping = dict(classes_by_timesteps)
    futs = []
    try:
        head_t, head_c = classes_by_timesteps[0]
        futs.append(b.submit(_qreq(head_t, head_c)))
        eng.entered.acquire(timeout=10)  # worker is inside solve #1
        for t, c in classes_by_timesteps[1:]:
            futs.append(b.submit(_qreq(t, c)))
        for _ in classes_by_timesteps[1:]:
            eng.release.release()
            eng.entered.acquire(timeout=10)
        eng.release.release()  # let the last solve return
        for f in futs:
            f.result(30)
    finally:
        eng.release.release()
        b.close()
    return [mapping[t] for t in eng.order]


class TestWDRR:
    def test_single_class_is_plain_arrival_order_fifo(self):
        plan = [(3 + i, "batch") for i in range(6)]
        assert _drive(plan) == ["batch"] * 6
        # and the engine saw strict arrival order (no reordering cost
        # for the pre-QoS single-tenant deployment)
        eng = _GateEngine()
        b = DynamicBatcher(eng, max_wait=0.001)
        try:
            futs = [b.submit(_qreq(3 + i, "batch")) for i in range(6)]
            eng.entered.acquire(timeout=10)
            for _ in range(5):
                eng.release.release()
                eng.entered.acquire(timeout=10)
            eng.release.release()
            for f in futs:
                f.result(30)
        finally:
            eng.release.release()
            b.close()
        assert eng.order == sorted(eng.order)

    def test_interactive_flood_does_not_starve_best_effort(self):
        # 40 interactive stacked against 2 best_effort: DRR's bound
        # serves best_effort at least once every ~sum(weights)=17
        # picks, so BOTH drain well before the flood does.
        plan = [(100, "best_effort"), (101, "best_effort")]
        plan += [(3 + i, "interactive") for i in range(40)]
        # head item (occupying the worker) is interactive so the two
        # best_effort submissions land in an already-contended stash
        plan = [plan[2]] + plan[:2] + plan[3:]
        order = _drive(plan)
        be = [i for i, c in enumerate(order) if c == "best_effort"]
        assert len(be) == 2
        # contention holds them back at first (interactive outbids)...
        assert be[0] > 1
        # ...but the starvation bound (one best_effort turn per
        # ~sum(weights) picks) drains both long before the flood ends
        assert be[0] <= 17
        assert be[-1] <= 2 * 17
        assert be[-1] < len(order) - 1

    def test_fresh_interactive_beats_backlogged_lower_class(self):
        # strict rule: an eligible interactive request takes the NEXT
        # pick ahead of a backlogged batch queue - its first-round
        # 16-credit outbids any deficit batch can have banked.
        eng = _GateEngine()
        b = DynamicBatcher(eng, max_wait=0.001)
        try:
            f0 = b.submit(_qreq(50, "batch"))
            eng.entered.acquire(timeout=10)
            futs = [b.submit(_qreq(3 + i, "batch")) for i in range(4)]
            fi = b.submit(_qreq(40, "interactive"))
            eng.release.release()            # finish the head batch
            eng.entered.acquire(timeout=10)  # pick #2 is now chosen
            for _ in range(4):
                eng.release.release()
                eng.entered.acquire(timeout=10)
            eng.release.release()
            f0.result(30)
            fi.result(30)
            for f in futs:
                f.result(30)
        finally:
            eng.release.release()
            b.close()
        assert eng.order[1] == 40  # the interactive one, next pass

    def test_class_counters_land_in_the_registry(self):
        m = ServeMetrics()
        eng = _GateEngine()
        b = DynamicBatcher(eng, metrics=m, max_wait=0.001)
        try:
            f = b.submit(_qreq(3, "interactive"))
            eng.entered.acquire(timeout=10)
            eng.release.release()
            f.result(30)
        finally:
            eng.release.release()
            b.close()
        assert m._class_requests.value(
            **{"class": "interactive"}
        ) == 1
        assert m._scheduled.value(**{"class": "interactive"}) == 1


# ---- brownout ladder ----

class TestBrownout:
    def _hot(self, bo, n=10, wait=1.0):
        for _ in range(n):
            bo.observe_wait(wait)

    def test_rejects_malformed_thresholds(self):
        with pytest.raises(ValueError):
            BrownoutController(thresholds=(1.0, 2.0))
        with pytest.raises(ValueError):
            BrownoutController(thresholds=(3.0, 2.0, 1.0))
        with pytest.raises(ValueError):
            BrownoutController(thresholds=(0.0, 1.0, 2.0))

    def test_escalates_immediately_across_rungs(self):
        bo = BrownoutController(
            thresholds=(0.1, 0.2, 0.3), min_samples=4,
            min_interval_s=0.0,
        )
        assert bo.update() == 0  # too few samples: healthy
        self._hot(bo, wait=0.15)
        assert bo.update() == 1
        self._hot(bo, wait=5.0)
        assert bo.update() == 3  # straight to the top, no ladder-climb

    def test_shed_policy_never_touches_interactive(self):
        bo = BrownoutController(min_interval_s=0.0)
        for rung, sheds in ((0, set()), (1, {"best_effort"}),
                            (2, {"batch", "best_effort"}),
                            (3, {"batch", "best_effort"})):
            bo._rung = rung
            assert {c for c in sched.PRIORITY_CLASSES
                    if bo.sheds(c)} == sheds
        assert bo.defers_chunk_starts()  # still at rung 3
        bo._rung = 2
        assert not bo.defers_chunk_starts()

    def test_recovery_is_one_rung_at_a_time(self):
        bo = BrownoutController(
            thresholds=(0.1, 0.2, 0.3), min_samples=4,
            min_interval_s=0.0, cooldown_s=0.0, sample_ttl_s=0.2,
        )
        self._hot(bo, wait=5.0)
        assert bo.update() == 3
        time.sleep(0.25)  # the hot samples age out of the TTL window
        assert bo.update() == 2  # never 3 -> 0 in one step
        assert bo.update() == 1
        assert bo.update() == 0
        snap = bo.snapshot()
        assert snap["rung_name"] == "healthy"
        assert snap["thresholds_s"] == [0.1, 0.2, 0.3]

    def test_cooldown_gates_deescalation(self):
        bo = BrownoutController(
            thresholds=(0.1, 0.2, 0.3), min_samples=4,
            min_interval_s=0.0, cooldown_s=60.0, sample_ttl_s=0.2,
        )
        self._hot(bo, wait=5.0)
        assert bo.update() == 3
        time.sleep(0.25)
        assert bo.update() == 3  # healthy signal but inside cooldown

    def test_submit_sheds_with_measured_retry_after(self):
        bo = BrownoutController(
            thresholds=(0.01, 10.0, 20.0), min_samples=4,
            min_interval_s=0.0,
        )
        for _ in range(8):
            bo.observe_wait(0.5)
        m = ServeMetrics()
        b = DynamicBatcher(_GateEngine(), metrics=m, max_wait=0.001,
                           brownout=bo)
        try:
            with pytest.raises(ShedError) as ei:
                b.submit(_qreq(3, "best_effort"))
            assert ei.value.rung == "shed_best_effort"
            assert ei.value.retry_after_s > 0
            # interactive and batch still board at rung 1
            fi = b.submit(_qreq(4, "interactive"))
            fb = b.submit(_qreq(5, "batch"))
            eng = b.engine
            eng.entered.acquire(timeout=10)
            eng.release.release()
            eng.entered.acquire(timeout=10)
            eng.release.release()
            fi.result(30)
            fb.result(30)
        finally:
            b.engine.release.release()
            b.close()
        assert m.snapshot()["shed_total"] == 1
        assert m._shed.value(
            rung="shed_best_effort", **{"class": "best_effort"}
        ) == 1


# ---- the bitwise isolation drill ----

class TestIsolationDrill:
    """A best_effort chunked march preempted per-chunk by interactive
    traffic must finish BITWISE identical to its unloaded run - QoS
    reorders work, it never touches numerics."""

    THRESHOLD = 8
    CHUNK = 4

    @pytest.fixture(scope="class")
    def eng(self):
        return ServeEngine(bucket_sizes=(1, 2), interpret=True)

    def _batcher(self, eng):
        return DynamicBatcher(
            eng, max_wait=0.005, chunk_threshold=self.THRESHOLD,
            chunk_steps=self.CHUNK,
        )

    def test_preempted_low_priority_march_is_bitwise_identical(
        self, eng
    ):
        p = Problem(N=8, timesteps=17)
        b = self._batcher(eng)
        try:
            control = b.submit(
                SolveRequest(problem=p, lane=eb.LaneSpec(),
                             priority="best_effort")
            ).result(300)[0]
        finally:
            b.close()
        b = self._batcher(eng)
        short = Problem(N=8, timesteps=3)
        try:
            long_fut = b.submit(SolveRequest(
                problem=p, lane=eb.LaneSpec(), priority="best_effort",
            ))
            # interactive pressure throughout the march: each chunk
            # slot competes with a fresh interactive arrival
            shorts = []
            for i in range(6):
                shorts.append(b.submit(SolveRequest(
                    problem=short, lane=eb.LaneSpec(phase=1.0 + i),
                    priority="interactive",
                )))
                time.sleep(0.01)
            short_res = [f.result(300) for f in shorts]
            res, health, info = long_fut.result(300)
        finally:
            b.close()
        assert health is None
        assert info["chunked"] is True and info["chunks"] == 4
        assert all(h is None for _, h, _ in short_res)
        # the drill's point: identical bits, loaded or not
        assert np.array_equal(np.asarray(res.u_cur),
                              np.asarray(control.u_cur))
        assert np.array_equal(np.asarray(res.u_prev),
                              np.asarray(control.u_prev))
        assert np.array_equal(np.asarray(res.abs_errors),
                              np.asarray(control.abs_errors))


# ---- replica-side tenant trust over HTTP ----

@pytest.fixture(scope="module")
def qos_server():
    httpd, state = build_server(
        port=0, max_wait=0.05, default_kernel="roll", interpret=True,
        proxy_token="sek", tenant_inflight_cap=2,
    )
    thread = threading.Thread(target=httpd.serve_forever, daemon=True)
    thread.start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    yield base, state
    httpd.shutdown()
    state.batcher.close()
    httpd.server_close()


def _post(base, body, headers=None):
    req = urllib.request.Request(
        base + "/solve", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
    )
    try:
        with urllib.request.urlopen(req, timeout=120) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def _metric(base, name, **labels):
    """One sample's value from a live /metrics scrape (0.0 when the
    labeled sample has not been emitted yet)."""
    req = urllib.request.Request(
        base + "/metrics", headers={"Accept": "text/plain"}
    )
    with urllib.request.urlopen(req, timeout=30) as r:
        samples, _types = parse_prometheus(r.read().decode())
    for key, value in samples.items():
        sample = key if "{" in key else key + "{"
        sname, _, rest = sample.partition("{")
        if sname != name:
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            return value
    return 0.0


class TestReplicaTenantTrust:
    BODY = {"N": 8, "timesteps": 3, "kernel": "roll"}

    def test_spoofed_headers_are_ignored_and_counted(self, qos_server):
        base, _state = qos_server
        before = _metric(
            base, "wavetpu_serve_tenant_spoof_rejected_total"
        )
        code, payload, _h = _post(base, self.BODY, headers={
            "X-Wavetpu-Tenant": "mallory", "X-Priority": "interactive",
            "X-Wavetpu-Proxy-Token": "wrong",
        })
        assert code == 200 and payload["status"] == "ok"  # served...
        assert _metric(
            base, "wavetpu_serve_tenant_spoof_rejected_total"
        ) == before + 1  # ...but untenanted, and the spoof is counted
        assert _metric(
            base, "wavetpu_serve_tenant_requests_total",
            tenant="mallory",
        ) == 0.0
        assert _metric(
            base, "wavetpu_serve_class_requests_total",
            **{"class": "interactive"},
        ) == 0.0

    def test_router_token_unlocks_tenant_and_priority(self, qos_server):
        base, _state = qos_server
        code, payload, _h = _post(base, self.BODY, headers={
            "X-Wavetpu-Tenant": "alice", "X-Priority": "interactive",
            "X-Wavetpu-Proxy-Token": "sek",
        })
        assert code == 200 and payload["status"] == "ok"
        assert _metric(
            base, "wavetpu_serve_tenant_requests_total", tenant="alice",
        ) == 1.0
        assert _metric(
            base, "wavetpu_serve_class_requests_total",
            **{"class": "interactive"},
        ) == 1.0

    def test_body_priority_needs_no_token(self, qos_server):
        # priority in the BODY is the direct-client path: it only picks
        # a class (no tenant impersonation), so it needs no token
        base, _state = qos_server
        code, _p, _h = _post(
            base, {**self.BODY, "priority": "best_effort"}
        )
        assert code == 200
        assert _metric(
            base, "wavetpu_serve_class_requests_total",
            **{"class": "best_effort"},
        ) == 1.0

    def test_inflight_cap_acquire_release(self, qos_server):
        _base, state = qos_server
        assert state.try_acquire_tenant_slot("bob")
        assert state.try_acquire_tenant_slot("bob")
        assert not state.try_acquire_tenant_slot("bob")  # cap = 2
        assert state.try_acquire_tenant_slot("carol")  # per-tenant
        state.release_tenant_slot("bob")
        assert state.try_acquire_tenant_slot("bob")
        for _ in range(2):
            state.release_tenant_slot("bob")
        state.release_tenant_slot("carol")
        state.release_tenant_slot("ghost")  # never acquired: no-op

    def test_healthz_carries_the_brownout_block(self, qos_server):
        base, _state = qos_server
        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            payload = json.loads(r.read())
        bo = payload["brownout"]
        assert bo["rung"] == 0 and bo["rung_name"] == "healthy"
        assert len(bo["thresholds_s"]) == 3


# ---- router quota + priority stamping, end to end ----

class TestRouterQoS:
    BODY = {"N": 8, "timesteps": 3, "kernel": "roll"}
    CELLS = float(9 ** 3 * 3)

    @pytest.fixture(scope="class")
    def stack(self):
        from wavetpu.fleet.router import build_router
        httpd, state = build_server(
            port=0, max_wait=0.05, default_kernel="roll",
            interpret=True, proxy_token="sek",
        )
        threading.Thread(
            target=httpd.serve_forever, daemon=True
        ).start()
        member = f"http://127.0.0.1:{httpd.server_address[1]}"
        keys = {
            "vk": quota.TenantConfig(
                tenant="victim", priority="interactive",
            ),
            "ak": quota.TenantConfig(
                tenant="aggressor", priority="best_effort",
                priority_ceiling="best_effort",
                cells_per_s=self.CELLS, cells_burst=self.CELLS,
            ),
        }
        rh, rs = build_router(
            [member], poll_interval_s=0.5, api_keys=keys,
            proxy_token="sek",
        )
        threading.Thread(target=rh.serve_forever, daemon=True).start()
        base = f"http://127.0.0.1:{rh.server_address[1]}"
        yield base, member, rs
        rs.stop_poller()
        rh.shutdown()
        rh.server_close()
        httpd.shutdown()
        state.batcher.close()
        httpd.server_close()

    def test_quota_429_carries_refill_accurate_retry_after(
        self, stack
    ):
        base, _member, rs = stack
        # warm the program via the unlimited tenant so the aggressor's
        # two probes are back to back (a cold compile would refill the
        # bucket mid-measurement)
        code, _p, _h = _post(base, self.BODY, headers={"X-Api-Key": "vk"})
        assert code == 200
        hdr = {"X-Api-Key": "ak"}
        code, _p, _h = _post(base, self.BODY, headers=hdr)
        assert code == 200  # the full bucket covers request #1
        code, payload, h = _post(base, self.BODY, headers=hdr)
        assert code == 429
        assert payload["retriable"] is True
        retry = payload["retry_after_s"]
        # one full bucket of cells at CELLS/s refills in <= 1 s, and
        # most of it is still owed right after the spend
        assert 0.5 < retry <= 1.0
        assert h["Retry-After"] == str(max(1, int(retry + 0.5)))
        # honoring the measured value is sufficient: the bucket can
        # afford the request again exactly then
        time.sleep(retry)
        code, _p, _h = _post(base, self.BODY, headers=hdr)
        assert code == 200
        snap = rs.snapshot()
        assert snap["quota_rejected_per_tenant"]["aggressor"] >= 1

    def test_router_stamps_clamped_priority_downstream(self, stack):
        base, member, _rs = stack
        # the aggressor claims interactive; its ceiling is best_effort
        before = _metric(
            member, "wavetpu_serve_class_requests_total",
            **{"class": "best_effort"},
        )
        code = None
        for _ in range(4):  # ride out any bucket debt from prior tests
            code, _p, _h = _post(base, self.BODY, headers={
                "X-Api-Key": "ak", "X-Priority": "interactive",
            })
            if code == 200:
                break
            time.sleep(1.05)
        assert code == 200
        assert _metric(
            member, "wavetpu_serve_class_requests_total",
            **{"class": "best_effort"},
        ) == before + 1

    def test_victim_defaults_to_interactive(self, stack):
        base, member, _rs = stack
        before = _metric(
            member, "wavetpu_serve_class_requests_total",
            **{"class": "interactive"},
        )
        code, _p, _h = _post(base, self.BODY, headers={
            "X-Api-Key": "vk",
        })
        assert code == 200
        assert _metric(
            member, "wavetpu_serve_class_requests_total",
            **{"class": "interactive"},
        ) == before + 1
        assert _metric(
            member, "wavetpu_serve_tenant_requests_total",
            tenant="victim",
        ) >= 1.0

    def test_router_metrics_render_quota_counters(self, stack):
        base, _member, _rs = stack
        req = urllib.request.Request(
            base + "/metrics", headers={"Accept": "text/plain"}
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            text = r.read().decode()
        assert "wavetpu_router_quota_rejected_total" in text
        assert 'wavetpu_router_tenant_quota_rejected_total' \
            '{tenant="aggressor"}' in text


# ---- loadgen: tenants mix, per-tenant report + gate ----

class TestLoadgenQoS:
    def _scenarios(self):
        return trace.default_scenarios(n=8, timesteps=6)

    def test_gen_tenants_is_deterministic_and_labeled(self):
        kw = dict(victim_key="vk", aggressor_key="ak",
                  aggressor_mult=4)
        a = trace.generate("tenants", 10.0, 4.0,
                           scenarios=self._scenarios(), seed=7, **kw)
        b = trace.generate("tenants", 10.0, 4.0,
                           scenarios=self._scenarios(), seed=7, **kw)
        assert a == b
        tenants = {r["tenant"] for r in a}
        assert tenants == {"victim", "aggressor"}
        for r in a:
            if r["tenant"] == "victim":
                assert r["priority"] == "interactive"
                assert r["api_key"] == "vk"
            else:
                assert r["priority"] == "best_effort"
                assert r["api_key"] == "ak"
                assert r["body"]["timesteps"] == 6 * 4
        assert [r["t"] for r in a] == sorted(r["t"] for r in a)

    def test_trace_roundtrip_preserves_qos_fields(self, tmp_path):
        records = trace.generate(
            "tenants", 5.0, 4.0, scenarios=self._scenarios(), seed=3,
            victim_key="vk", aggressor_key="ak",
        )
        path = str(tmp_path / "t.jsonl")
        trace.save_scenario_trace(path, records)
        loaded = trace.load_scenario_trace(path)
        assert [r.get("tenant") for r in loaded] \
            == [r["tenant"] for r in records]
        assert [r.get("priority") for r in loaded] \
            == [r["priority"] for r in records]

    def _outcome(self, i, status, tenant, priority, latency=0.01):
        return runner.RequestOutcome(
            index=i, scenario="s", request_id=f"r{i}", status=status,
            latency_s=latency, t_sent=0.0, tenant=tenant,
            priority=priority,
        )

    def _report(self, outcomes):
        result = runner.ReplayResult(
            outcomes=outcomes, warmup_outcomes=[], metrics_before={},
            metrics_after={}, wall_seconds=1.0, mode="open",
            concurrency=1, speed=1.0, targets=["http://x"],
        )
        return lg_report.build_report(result, target="http://x")

    def test_report_breaks_down_by_tenant_and_class(self):
        outs = [
            self._outcome(0, 200, "victim", "interactive"),
            self._outcome(1, 200, "victim", "interactive"),
            self._outcome(2, 429, "aggressor", "best_effort"),
            self._outcome(3, 500, "aggressor", "best_effort"),
        ]
        report = self._report(outs)
        v = report["tenants"]["victim"]
        a = report["tenants"]["aggressor"]
        assert v["requests"] == 2 and v["errors"] == 0
        assert v["error_rate"] == 0.0 and v["p95_ms"] is not None
        assert a["rejected_429"] == 1 and a["errors"] == 1
        assert a["reject_rate"] == 0.5 and a["error_rate"] == 0.5
        assert report["classes"]["interactive"]["requests"] == 2
        assert report["classes"]["best_effort"]["requests"] == 2

    def test_untenanted_report_keeps_its_pre_qos_shape(self):
        report = self._report([
            self._outcome(0, 200, "", ""),
            self._outcome(1, 200, "", ""),
        ])
        assert "tenants" not in report
        assert "classes" not in report

    def test_gate_enforces_tenant_slos(self):
        report = self._report([
            self._outcome(0, 200, "victim", "interactive", 0.010),
            self._outcome(1, 500, "victim", "interactive", 0.500),
            self._outcome(2, 429, "aggressor", "best_effort"),
        ])
        # relax the aggregate budgets so only the tenant_slos speak:
        # the crafted 500 would otherwise also fire DEFAULT_SLO's
        # strict overall error_budget=0
        slo = {"error_budget": 1.0, "reject_budget": 1.0, "tenant_slos": {
            "victim": {"error_budget": 0.0, "p95_budget_ms": 100.0},
            "aggressor": {"reject_budget": 0.0},
            "ghost": {"error_budget": 0.0},
        }}
        names = {v["slo"] for v in lg_report.gate(report, slo=slo)}
        assert names == {
            "tenant:victim:error_budget",
            "tenant:victim:p95_budget_ms",
            "tenant:aggressor:reject_budget",
            "tenant:ghost",
        }
        # the passing configuration is quiet
        ok = {"error_budget": 1.0, "reject_budget": 1.0,
              "tenant_slos": {"victim": {"p95_budget_ms": 1000.0}}}
        assert lg_report.gate(report, slo=ok) == []
        # and the gate text surfaces the breakdown
        text = lg_report.format_gate(
            lg_report.gate(report, slo=ok), report, None
        )
        assert "tenant:victim" in text

    def test_gate_rejects_unknown_tenant_slo_keys(self):
        report = self._report([
            self._outcome(0, 200, "victim", "interactive"),
        ])
        with pytest.raises(ValueError, match="unknown tenant SLO"):
            lg_report.gate(report, slo={
                "tenant_slos": {"victim": {"p50_budget_ms": 1.0}},
            })

    def test_cli_parses_repeatable_tenant_slo_flags(self):
        from wavetpu.loadgen.cli import _parse_tenant_slos
        parsed = _parse_tenant_slos([
            "victim:error-budget=0",
            "victim:p95-budget-ms=150",
            "aggressor:reject-budget=0.5",
        ])
        assert parsed == {
            "victim": {"error_budget": 0.0, "p95_budget_ms": 150.0},
            "aggressor": {"reject_budget": 0.5},
        }
        for bad in ("victim", "victim:error-budget", "x=1",
                    "victim:p50-budget-ms=1"):
            with pytest.raises(ValueError):
                _parse_tenant_slos([bad])


class TestCheckpointPriorityStickiness:
    def test_put_records_priority_in_meta(self, tmp_path):
        # a preempted best_effort march stays best_effort across a
        # handoff however the resume request is labeled (the class was
        # clamped at ORIGINAL admission)
        from wavetpu.serve.preempt import SolveStateStore
        store = SolveStateStore(str(tmp_path / "state"))
        token = store.put(
            {"N": 8, "timesteps": 17, "chunk_len": 4},
            [np.zeros((9, 9, 9), np.float32)] * 2,
            4,
            np.zeros(18, np.float64), np.zeros(18, np.float64),
            priority="best_effort",
        )
        meta = store.load(token)[0]
        assert meta["priority"] == "best_effort"
