"""Persistent AOT program cache contracts (wavetpu/serve/progcache.py).

The acceptance drills: a subprocess warms a cache via `wavetpu warmup
--manifest` and the parent then serves the same tiers with ZERO fresh
compiles and bitwise-identical output; corruption (truncation, stale
fingerprint - driven through the WAVETPU_FAULT chaos harness, so the
REAL rejection branches fire) and over-budget GC are counted misses
that recompile cleanly, never crashes and never circuit-breaker trips;
the ledger's measured `source: disk` accounting activates without
disturbing the old-format what-if pin.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from wavetpu.core.problem import Problem
from wavetpu.obs import ledger, telemetry
from wavetpu.run import faults
from wavetpu.serve import progcache
from wavetpu.serve.engine import ServeEngine


def _lane():
    from wavetpu.ensemble.batched import LaneSpec

    return LaneSpec()


def _tiny_problem():
    return Problem(N=8, timesteps=4)


def _solve(engine, timing=None):
    result, health = engine.solve(_tiny_problem(), [_lane()],
                                  timing=timing)
    assert health == [None]
    return np.asarray(result.results[0].u_cur)


def _key(**over):
    base = dict(
        N=8, Lx=1.0, Ly=1.0, Lz=1.0, T=1.0, timesteps=4,
        scheme="standard", path="roll", k=1, dtype="f32",
        with_field=False, compute_errors=True, batch=1, mesh=None,
    )
    base.update(over)
    return base


aot_ok = progcache.aot_capability()[0]
needs_aot = pytest.mark.skipif(
    not aot_ok, reason="jaxlib cannot serialize executables here"
)


@needs_aot
class TestDiskTier:
    def test_second_engine_adopts_from_disk_bitwise(self, tmp_path):
        """The tentpole in two instances: engine A compiles and stores;
        engine B (a 'restarted replica') adopts from disk with zero
        fresh compiles, and the solve is bitwise identical to a fresh
        twin's."""
        d = str(tmp_path / "cache")
        a = ServeEngine(bucket_sizes=(1,), interpret=True,
                        program_cache_dir=d)
        t = {}
        u_a = _solve(a, t)
        assert t["warm"] == "false"
        assert a.misses == 1 and a.disk_hits == 0
        assert a.progcache.counts.get("store") == 1

        b = ServeEngine(bucket_sizes=(1,), interpret=True,
                        program_cache_dir=d)
        t = {}
        u_b = _solve(b, t)
        assert t["warm"] == "disk"
        assert b.misses == 0 and b.disk_hits == 1
        # deserialize wall, not an XLA compile
        assert t["compile_seconds"] < 5.0

        fresh = ServeEngine(bucket_sizes=(1,), interpret=True)
        u_fresh = _solve(fresh)
        assert np.array_equal(u_a, u_b)
        assert np.array_equal(u_b, u_fresh)

    def test_memory_hit_still_wins_over_disk(self, tmp_path):
        d = str(tmp_path / "cache")
        eng = ServeEngine(bucket_sizes=(1,), interpret=True,
                          program_cache_dir=d)
        _solve(eng)
        t = {}
        _solve(eng, t)
        assert t["warm"] == "true"  # the test_serve pin's label
        assert eng.hits == 1 and eng.disk_hits == 0

    def test_cache_stats_exposes_disk_tier(self, tmp_path):
        d = str(tmp_path / "cache")
        eng = ServeEngine(bucket_sizes=(1,), interpret=True,
                          program_cache_dir=d)
        _solve(eng)
        stats = eng.cache_stats()
        assert stats["disk_hits"] == 0
        pc = stats["progcache"]
        assert pc["enabled"] is True and pc["aot"] is True
        assert pc["entries"] == 1 and pc["bytes"] > 0
        assert pc["aot_probes"][0]["probe"] == "aot_serialize_executable"
        assert pc["aot_probes"][0]["ok"] is True
        off = ServeEngine(bucket_sizes=(1,), interpret=True)
        assert off.cache_stats()["progcache"] == {"enabled": False}

    def test_disk_hit_writes_source_disk_ledger_line(self, tmp_path):
        d = str(tmp_path / "cache")
        warm = ServeEngine(bucket_sizes=(1,), interpret=True,
                           program_cache_dir=d)
        _solve(warm)
        tel_d = str(tmp_path / "tel")
        tel = telemetry.start(tel_d, interval=60.0)
        try:
            eng = ServeEngine(bucket_sizes=(1,), interpret=True,
                              program_cache_dir=d)
            _solve(eng)
        finally:
            tel.stop()
        entries = ledger.load_ledger(
            os.path.join(tel_d, ledger.LEDGER_FILENAME)
        )
        assert [e.get("source") for e in entries] == ["disk"]
        assert entries[0]["fresh_compile_s"] > 0


@needs_aot
class TestCorruptionDrills:
    def _warm_cache(self, tmp_path):
        d = str(tmp_path / "cache")
        eng = ServeEngine(bucket_sizes=(1,), interpret=True,
                          program_cache_dir=d)
        u = _solve(eng)
        return d, u

    def test_truncated_entry_is_counted_miss(self, tmp_path):
        """Direct on-disk truncation (no harness): checksum/length
        rejection -> counted corrupt -> clean fresh recompile."""
        d, u_ref = self._warm_cache(tmp_path)
        (entry,) = [
            os.path.join(d, n) for n in os.listdir(d)
            if n.endswith(progcache.ENTRY_SUFFIX)
        ]
        faults.truncate_tail(entry, drop_bytes=64)
        eng = ServeEngine(bucket_sizes=(1,), interpret=True,
                          program_cache_dir=d)
        t = {}
        u = _solve(eng, t)
        assert t["warm"] == "false"  # fresh compile, not a crash
        assert eng.misses == 1 and eng.disk_hits == 0
        assert eng.progcache.counts.get("corrupt") == 1
        # Self-healing: the corrupt entry was deleted and the fresh
        # recompile stored in its place (the suite runs with JAX's
        # compilation cache off, so the recompile is a real compile and
        # may be serialized), so the NEXT replica adopts it.
        assert eng.progcache.counts.get("store") == 1
        again = ServeEngine(bucket_sizes=(1,), interpret=True,
                            program_cache_dir=d)
        t = {}
        u2 = _solve(again, t)
        assert t["warm"] == "disk"
        assert again.progcache.counts.get("disk_hit") == 1
        assert np.array_equal(u, u_ref) and np.array_equal(u2, u_ref)

    def test_fault_harness_truncate_counted_never_breaker(self, tmp_path):
        """`serve-progcache-truncate` (WAVETPU_FAULT grammar) truncates
        the REAL entry file just before the read: the genuine
        checksum branch rejects it, the request recompiles, and the
        circuit breaker never hears about it."""
        d, _ = self._warm_cache(tmp_path)
        plan = faults.parse_serve_spec("serve-progcache-truncate:count=1")
        assert plan is not None
        eng = ServeEngine(bucket_sizes=(1,), interpret=True,
                          program_cache_dir=d, fault_plan=plan)
        t = {}
        _solve(eng, t)
        assert t["warm"] == "false"
        assert eng.progcache.counts.get("corrupt") == 1
        assert eng.breaker is not None
        snap = eng.breaker.snapshot()
        assert snap["open"] == 0 and snap["keys"] == []

    def test_fault_harness_fingerprint_mismatch(self, tmp_path):
        """`serve-progcache-fingerprint` poisons the EXPECTED
        fingerprint for one load - the real cross-version rejection
        branch fires as a counted miss, then recompiles."""
        d, _ = self._warm_cache(tmp_path)
        plan = faults.parse_serve_spec(
            "serve-progcache-fingerprint:count=1"
        )
        eng = ServeEngine(bucket_sizes=(1,), interpret=True,
                          program_cache_dir=d, fault_plan=plan)
        t = {}
        _solve(eng, t)
        assert t["warm"] == "false"
        assert eng.progcache.counts.get("fingerprint_mismatch") == 1
        assert eng.breaker.snapshot()["open"] == 0
        # budget spent: the next replica adopts normally
        eng2 = ServeEngine(bucket_sizes=(1,), interpret=True,
                           program_cache_dir=d, fault_plan=plan)
        t = {}
        _solve(eng2, t)
        assert t["warm"] == "disk"

    def test_env_fingerprint_keys_the_filename(self, tmp_path):
        """A different fingerprint means a different FILENAME - a
        cross-version entry is never even opened (disk_miss, not
        corrupt)."""
        cache = progcache.ProgramCache(str(tmp_path / "c"))
        assert cache.put(_key(), {"triple": b"x" * 64}, 1.0)
        other = progcache.ProgramCache(str(tmp_path / "c"))
        other._fp_hash = "deadbeef"
        assert other.load(_key()) is None
        assert other.counts.get("disk_miss") == 1

    def test_entry_from_an_older_version_is_not_adopted(
        self, tmp_path, monkeypatch
    ):
        """An entry written under 0.1.0, whose lane programs always
        masked, is never adopted: the version is in the fingerprint,
        so the current one misses it and compiles its own program."""
        import wavetpu

        current = wavetpu.__version__
        assert current != "0.1.0"
        monkeypatch.setattr(wavetpu, "__version__", "0.1.0")
        d, _ = self._warm_cache(tmp_path)
        monkeypatch.setattr(wavetpu, "__version__", current)
        eng = ServeEngine(bucket_sizes=(1,), interpret=True,
                          program_cache_dir=d)
        t = {}
        result, _ = eng.solve(_tiny_problem(), [_lane()], timing=t)
        assert t["warm"] == "false"
        assert eng.progcache.counts.get("disk_miss") == 1
        assert result.masked is False


class TestGC:
    def test_over_budget_evicts_oldest_newest_survives(self, tmp_path):
        cache = progcache.ProgramCache(str(tmp_path / "c"))
        paths = []
        for i in range(3):
            k = _key(batch=i + 1)
            assert cache.put(k, {"blob": b"x" * 4096}, 1.0)
            p = cache.entry_path(k)
            os.utime(p, (100.0 + i, 100.0 + i))  # deterministic LRU
            paths.append(p)
        sizes = [os.path.getsize(p) for p in paths]
        cache.max_bytes = sizes[1] + sizes[2]  # room for exactly two
        assert cache.gc() == 1
        assert not os.path.exists(paths[0])
        assert os.path.exists(paths[1]) and os.path.exists(paths[2])
        assert cache.counts.get("gc_evict") == 1

    def test_budget_smaller_than_one_entry_keeps_latest(self, tmp_path):
        cache = progcache.ProgramCache(str(tmp_path / "c"), max_bytes=1)
        for i in range(2):
            k = _key(batch=i + 1)
            cache.put(k, {"blob": b"x" * 4096}, 1.0)
            os.utime(cache.entry_path(k), (100.0 + i, 100.0 + i))
        cache.gc()
        remaining = [n for n in os.listdir(cache.directory)
                     if n.endswith(progcache.ENTRY_SUFFIX)]
        assert len(remaining) == 1  # keep-latest, never keep-nothing
        assert os.path.basename(
            cache.entry_path(_key(batch=2))
        ) in remaining

    def test_hit_refreshes_lru_clock(self, tmp_path):
        if not aot_ok:
            pytest.skip("load() needs AOT mode")
        cache = progcache.ProgramCache(str(tmp_path / "c"))
        for i in range(2):
            k = _key(batch=i + 1)
            cache.put(k, {"blob": b"x" * 64}, 1.0)
            os.utime(cache.entry_path(k), (100.0 + i, 100.0 + i))
        assert cache.load(_key(batch=1)) is not None  # touch oldest
        entries = sorted(cache._entries(), key=lambda e: e[2])
        assert entries[-1][0] == cache.entry_path(_key(batch=1))


@needs_aot
class TestWarmupCLI:
    def _manifest(self, tmp_path):
        lp = str(tmp_path / "compile_ledger.jsonl")
        led = ledger.CompileLedger(lp)
        led.record(_key(), 1.0, ts=1.0, pid=1)
        led.close()
        manifest = ledger.warmup_manifest(ledger.load_ledger(lp))
        mp = str(tmp_path / "warmup_manifest.json")
        with open(mp, "w") as f:
            json.dump(manifest, f)
        return mp

    def test_round_trip_second_run_all_disk_hits(self, tmp_path, capsys):
        mp = self._manifest(tmp_path)
        d = str(tmp_path / "cache")
        assert progcache.main(
            ["--manifest", mp, "--program-cache-dir", d]
        ) == 0
        out = capsys.readouterr().out
        assert "compiled" in out and "-> cached" in out
        assert progcache.main(
            ["--manifest", mp, "--program-cache-dir", d]
        ) == 0
        out = capsys.readouterr().out
        assert "disk hit" in out
        assert "1 disk hit(s), 0 compiled" in out

    def test_usage_errors(self, tmp_path, capsys):
        assert progcache.main([]) == 2  # no --manifest
        bad = tmp_path / "bad.json"
        bad.write_text("{}")
        assert progcache.main(["--manifest", str(bad)]) == 2
        assert progcache.main(
            ["--manifest", str(tmp_path / "missing.json")]
        ) == 2
        capsys.readouterr()

    def test_oversized_mesh_key_skipped_not_failed(self, tmp_path,
                                                   capsys):
        manifest = {
            ledger.MANIFEST_FLAG: True, "version": 1,
            "keys": [ledger.normalize_key(_key(mesh=[64, 64, 64]))],
        }
        mp = str(tmp_path / "m.json")
        with open(mp, "w") as f:
            json.dump(manifest, f)
        assert progcache.main(
            ["--manifest", mp,
             "--program-cache-dir", str(tmp_path / "c")]
        ) == 0  # skip, not failure
        assert "skip (mesh needs" in capsys.readouterr().out


@needs_aot
class TestCrossProcess:
    def test_subprocess_warms_parent_serves_zero_fresh(self, tmp_path):
        """The cross-process acceptance drill: process A (a real
        subprocess) pre-populates the cache from a ledger-report
        manifest; process B (here) serves the same tier with zero
        fresh compiles, a ledger of only `source: disk`, and output
        bitwise identical to a fresh twin."""
        # a ledger naming the tier, exactly as ledger-report emits it
        lp = str(tmp_path / "compile_ledger.jsonl")
        led = ledger.CompileLedger(lp)
        led.record(_key(), 1.0, ts=1.0, pid=1)
        led.close()
        mp = str(tmp_path / "warmup_manifest.json")
        assert ledger.main([lp, "--emit-warmup-manifest", mp]) == 0
        d = str(tmp_path / "cache")
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, "-m", "wavetpu.cli", "warmup",
             "--manifest", mp, "--program-cache-dir", d],
            capture_output=True, text=True, env=env, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr
        assert "1 compiled" in proc.stdout or "compiled" in proc.stdout
        assert any(n.endswith(progcache.ENTRY_SUFFIX)
                   for n in os.listdir(d))

        tel_d = str(tmp_path / "tel")
        tel = telemetry.start(tel_d, interval=60.0)
        try:
            eng = ServeEngine(bucket_sizes=(1,), interpret=True,
                              program_cache_dir=d)
            t = {}
            u = _solve(eng, t)
        finally:
            tel.stop()
        assert t["warm"] == "disk"
        assert eng.misses == 0 and eng.disk_hits == 1
        entries = ledger.load_ledger(
            os.path.join(tel_d, ledger.LEDGER_FILENAME)
        )
        assert {e.get("source") for e in entries} == {"disk"}
        fresh = ServeEngine(bucket_sizes=(1,), interpret=True)
        assert np.array_equal(u, _solve(fresh))


class TestMeasuredLedger:
    def test_aggregate_partitions_disk_records(self):
        """`source: disk` lines feed ONLY the measured block; the
        what-if and every fresh-compile figure aggregate over the rest
        exactly as an old-format ledger would."""
        old = [
            {"key": _key(), "compile_s": 30.0, "cold": True,
             "ts": 1.0, "pid": 1},
            {"key": _key(), "compile_s": 28.0, "cold": True,
             "ts": 10.0, "pid": 2},
        ]
        mixed = old + [
            {"key": _key(), "compile_s": 0.05, "cold": True,
             "ts": 20.0, "pid": 3, "source": "disk",
             "fresh_compile_s": 28.0},
            {"key": _key(batch=8), "compile_s": 0.02, "cold": True,
             "ts": 21.0, "pid": 3, "source": "disk"},
        ]
        base = ledger.aggregate(old)
        agg = ledger.aggregate(mixed)
        mp = agg.pop("measured_persistent_cache")
        base.pop("measured_persistent_cache")
        assert agg == base  # disk lines invisible to the old math
        assert mp["disk_hits"] == 2
        assert mp["load_s"] == pytest.approx(0.07)
        assert mp["measured_saved_s"] == pytest.approx(28.0 - 0.05)
        assert mp["unattributed_hits"] == 1  # the no-fresh_compile_s one

    def test_report_line_only_with_disk_hits(self, capsys):
        recs = [{"key": _key(), "compile_s": 30.0, "cold": True,
                 "ts": 1.0, "pid": 1}]
        out = ledger.format_report(ledger.aggregate(recs))
        assert "measured persistent cache" not in out
        recs.append({"key": _key(), "compile_s": 0.05, "cold": True,
                     "ts": 2.0, "pid": 2, "source": "disk",
                     "fresh_compile_s": 30.0})
        out = ledger.format_report(ledger.aggregate(recs))
        assert "measured persistent cache: 1 disk hit(s)" in out


class TestLoadgenGate:
    def _report(self, cold):
        return {
            "loadgen_report": True, "requests": 4, "ok": 4,
            "latency_ms": {"p99_ms": 10.0},
            "error_rate": 0.0, "reject_rate": 0.0,
            "requests_per_s": 10.0,
            "server": {"cold_compiles": cold, "disk_hits": 2,
                       "warm_hits": 7},
        }

    def test_max_cold_compiles_gate(self):
        from wavetpu.loadgen import report as lg_report

        assert lg_report.gate(
            self._report(0), slo={"max_cold_compiles": 0}
        ) == []
        (v,) = lg_report.gate(
            self._report(2), slo={"max_cold_compiles": 0}
        )
        assert v["slo"] == "max_cold_compiles" and v["observed"] == 2
        # not gated unless asked (default None)
        assert lg_report.gate(self._report(5)) == []

    def test_format_gate_prints_compile_traffic(self):
        from wavetpu.loadgen import report as lg_report

        text = lg_report.format_gate([], self._report(0))
        assert "0 fresh, 2 disk hit(s), 7 warm hit(s)" in text


class TestAotProbe:
    def test_probe_is_cached_and_recorded(self):
        v1 = progcache.aot_capability()
        v2 = progcache.aot_capability()
        assert v1 is v2  # once per process
        (row,) = progcache.probe_results()
        assert row["probe"] == "aot_serialize_executable"
        assert row["ok"] == v1[0]

    def test_probe_passes_with_several_devices(self):
        """The suite sees 8 devices: the probe round-trips a one-device
        program anyway (it used to load it onto all 8 and fail)."""
        import jax

        assert len(jax.devices()) > 1
        assert progcache.aot_capability() == (True, None)

    def test_program_loads_back_onto_its_own_device(self):
        import pickle

        import jax
        import jax.numpy as jnp

        dev = jax.devices()[3]
        x = jax.device_put(jnp.arange(4.0), dev)
        compiled = jax.jit(lambda v: v + 1.0).lower(x).compile()
        again = progcache.load_executable(pickle.loads(pickle.dumps(
            progcache.serialize_executable(compiled)
        )))
        out = again(x)
        assert out.devices() == {dev}
        np.testing.assert_array_equal(np.asarray(out), [1.0, 2.0, 3.0, 4.0])


class TestJaxCache:
    """wavetpu/jaxcache.py: one place decides where JAX's compilation
    cache lives."""

    @pytest.fixture
    def cache_on(self):
        import jax
        from jax.experimental.compilation_cache import compilation_cache

        saved = (jax.config.jax_enable_compilation_cache,
                 jax.config.jax_compilation_cache_dir)
        jax.config.update("jax_enable_compilation_cache", True)
        yield jax
        jax.config.update("jax_enable_compilation_cache", saved[0])
        jax.config.update("jax_compilation_cache_dir", saved[1])
        compilation_cache.reset_cache()

    def test_env_dir_wins_and_code_sets_none(self, cache_on, monkeypatch,
                                             tmp_path):
        from wavetpu import jaxcache

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        before = cache_on.config.jax_compilation_cache_dir
        assert jaxcache.configure() == str(tmp_path)
        assert cache_on.config.jax_compilation_cache_dir == before

    def test_default_is_the_checkout_dir(self, cache_on, monkeypatch):
        from wavetpu import jaxcache

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        want = os.path.join(repo, ".jax_cache")
        assert jaxcache.configure() == want
        assert cache_on.config.jax_compilation_cache_dir == want

    def test_none_when_the_process_turned_it_off(self):
        from wavetpu import jaxcache

        assert jaxcache.configure() is None  # the suite runs with it off
