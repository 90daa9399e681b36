"""Roofline attribution + device-memory watermarks (wavetpu/obs/perf.py).

Pins: the shared cost model reproduces the BENCH-documented per-row
traffic figures and agrees with `choose_kstep_block`'s block choice;
modeled GB/s is reported for every instrumented solver path
(roll / pallas 1-step / k-fused / comp / sharded) plus the serve
execute span, and the roofline fraction against the chip's published
peak (None off the TPU); memory sampling keeps the None-on-unsupported contract
and the watermark/warn machinery works against a fake stats provider.
"""

import json
import os

import pytest

from wavetpu.core.problem import Problem
from wavetpu.obs import perf, telemetry, tracing
from wavetpu.obs.registry import MetricsRegistry, get_registry


def _fake_device(monkeypatch, platform, kind):
    """Make obs/perf see one device of `platform`/`kind` as device 0."""
    import types

    dev = types.SimpleNamespace(platform=platform, device_kind=kind)
    monkeypatch.setitem(
        __import__("sys").modules, "jax",
        types.SimpleNamespace(devices=lambda: [dev]),
    )


class TestCostModel:
    @pytest.mark.parametrize("kw,want", [
        # The N=512 traffic models, per path.
        (dict(path="kfused", k=4, n=512), 8.0),
        (dict(path="kfused", k=2, n=512), 10.0),
        (dict(path="kfused", k=4, n=512, itemsize=2), 3.0),
        (dict(path="kfused", k=4, n=512, with_field=True, block_x=4),
         11.0),
        (dict(path="kfused", k=2, n=512, with_field=True), 16.0),
        (dict(path="kfused_comp", k=4, n=512), 9.0),
        (dict(path="kfused_comp", k=2, n=512), 14.0),
        (dict(path="kfused_comp", k=4, n=512, v_itemsize=2,
              carry=False), 6.0),
        (dict(path="kfused_comp", k=2, n=512, v_itemsize=2, carry=False,
              with_field=True), 13.0),
        (dict(path="pallas"), 12.0),
        (dict(path="roll"), 12.0),
        (dict(path="leapfrog"), 12.0),
        (dict(path="pallas", with_field=True), 16.0),
        (dict(path="pallas", itemsize=2), 6.0),
        (dict(path="compensated"), 24.0),
        (dict(path="sharded"), 12.0),
        (dict(path="sharded", scheme="compensated"), 24.0),
        (dict(path="sharded_kfused", k=4, n=512), 8.0),
        (dict(path="kfused_comp_sharded", k=2, n=512), 14.0),
    ])
    def test_bench_documented_models(self, kw, want):
        assert perf.model_bytes_per_cell(**kw) == want

    def test_onion_model_reads_the_choosers_block(self):
        """Modeled-bytes agreement with choose_kstep_block's accounting:
        the onion model's bx IS the chooser's verdict, so model and
        kernel pipeline can never drift."""
        from wavetpu.kernels.stencil_pallas import (
            choose_kstep_block,
            choose_kstep_comp_block,
        )

        for n, k, itemsize in ((512, 4, 4), (512, 2, 4), (512, 4, 2),
                               (64, 2, 4)):
            bx = choose_kstep_block(n, k, itemsize)
            assert perf.model_bytes_per_cell(
                "kfused", k=k, n=n, itemsize=itemsize
            ) == itemsize * (4 * bx + 4 * k) / (k * bx)
        bx = choose_kstep_comp_block(512, 4, 4, 4, 4)
        assert perf.model_bytes_per_cell(
            "kfused_comp", k=4, n=512
        ) == ((2 * bx + 2 * 4) * 4 * 2 + 2 * bx * 2) / (4 * bx)
        # Sharded variants: the model takes the SAME depth/ghosts
        # arguments the sharded kernels pass their chooser, so a
        # ghost-shrunk block feeds the model too.
        bx = choose_kstep_block(512, 2, 4, depth=64, ghosts=True)
        assert perf.model_bytes_per_cell(
            "sharded_kfused", k=2, n=512, depth=64, ghosts=True
        ) == 4 * (4 * bx + 4 * 2) / (2 * bx)

    def test_no_model_when_onion_does_not_fit(self):
        # k=8 comp onion with field at N=512 f32 is over the ceiling at
        # every admissible bx: the honest answer is None, not a guess.
        assert perf.model_bytes_per_cell(
            "kfused_comp", k=8, n=512, with_field=True
        ) is None
        assert perf.solve_perf(10.0, "kfused_comp", k=8, n=512,
                               with_field=True) is None

    def test_solve_perf_fields(self, monkeypatch):
        _fake_device(monkeypatch, "tpu", "TPU v5 lite")
        rf = perf.solve_perf(40.0, "kfused", k=4, n=512)
        assert rf["model_bytes_per_cell"] == 8.0
        assert rf["model_gbps"] == 320.0
        assert rf["peak_gbps"] == 819.0  # published v5e HBM bandwidth
        assert rf["roofline_fraction"] == round(320.0 / 819.0, 4)
        assert rf["arithmetic_intensity"] == round(15.0 / 8.0, 4)
        assert perf.solve_perf(0.0, "kfused", k=4, n=512) is None

    def test_no_roofline_off_the_tpu(self, monkeypatch):
        _fake_device(monkeypatch, "cpu", "cpu")
        rf = perf.solve_perf(40.0, "kfused", k=4, n=512)
        assert rf["model_gbps"] == 320.0
        assert rf["peak_gbps"] is None
        assert rf["roofline_fraction"] is None  # not measured

    def test_unknown_tpu_kind_raises(self, monkeypatch):
        _fake_device(monkeypatch, "tpu", "TPU v99")
        with pytest.raises(ValueError, match="TPU v99"):
            perf.peak_gbps()


class TestRooflineRecording:
    def test_all_instrumented_paths_report_a_fraction(self):
        """Acceptance pin: after one solve per family (roll, pallas
        1-step, k-fused, comp, sharded), the process registry holds a
        positive modeled GB/s for every path label (and, on CPU, no
        roofline fraction)."""
        from wavetpu.kernels import stencil_pallas
        from wavetpu.solver import kfused, kfused_comp, leapfrog, sharded

        p = Problem(N=8, timesteps=3)
        leapfrog.solve(p)  # roll
        leapfrog.solve(
            p, step_fn=stencil_pallas.make_step_fn(interpret=True)
        )  # pallas 1-step (same "leapfrog" label, same 1-step model)
        leapfrog.solve_compensated(p)
        kfused.solve_kfused(p, k=2, interpret=True)
        kfused_comp.solve_kfused_comp(p, k=2, interpret=True)
        sharded.solve_sharded(p, mesh_shape=(1, 1, 1))
        g = get_registry().gauge(
            "wavetpu_solve_roofline_fraction", "", ("path",)
        )
        # 1-step variable-c: the ParamStep kernel must model the extra
        # field stream (16 B/cell, not 12) - gauge ratio pins it.  Big
        # enough that the 3-decimal rounding of model_gbps stays small
        # against the rate on a loaded CPU (N=8/3 read 16.66 under xdist).
        from wavetpu.kernels import stencil_ref

        pv = Problem(N=32, timesteps=100)
        field = stencil_ref.make_preset_c2tau2_field(pv, "constant")
        leapfrog.solve(
            pv, step_fn=stencil_ref.make_variable_c_step(field),
            compute_errors=False,
        )
        reg = get_registry()
        bpc = reg.gauge(
            "wavetpu_solve_model_gbps", "", ("path",)
        ).value(path="leapfrog") / reg.gauge(
            "wavetpu_last_solve_gcells_per_s", "", ("path",)
        ).value(path="leapfrog")
        # 0.5 slack: the gauge stores model_gbps rounded to 3 decimals,
        # which is coarse at CPU-scale throughput.
        assert abs(bpc - 16.0) < 0.5, bpc
        h = get_registry().histogram(
            "wavetpu_solve_gbps", "", ("path",),
            buckets=perf._GBPS_BUCKETS,
        )
        gbps = reg.gauge("wavetpu_solve_model_gbps", "", ("path",))
        for path in ("leapfrog", "compensated", "kfused", "kfused_comp",
                     "sharded"):
            assert gbps.value(path=path) > 0.0, path
            # CPU has no roofline: the fraction gauge stays unset.
            assert g.value(path=path) == 0.0, path
            assert h.count(path=path) >= 1, path

    def test_serve_execute_span_carries_roofline_attrs(self, tmp_path):
        from wavetpu.ensemble.batched import LaneSpec
        from wavetpu.serve.engine import ServeEngine

        d = str(tmp_path / "tel")
        tel = telemetry.start(d, interval=60.0)
        try:
            problem = Problem(N=8, timesteps=3)
            eng = ServeEngine(bucket_sizes=(1,), interpret=True)
            eng.solve(problem, [LaneSpec(phase=1.0)], path="roll")
        finally:
            tel.stop()
        spans = [
            json.loads(line)
            for line in open(os.path.join(d, "trace.jsonl"))
        ]
        ex = [s for s in spans if s.get("kind") == "serve.execute"]
        assert ex, "no serve.execute span"
        attrs = ex[-1]["attrs"]
        assert attrs["model_bytes_per_cell"] == 12.0
        assert attrs["model_gbps"] > 0.0
        assert attrs["roofline_fraction"] is None  # CPU: not measured
        # and the server registry carries the same gauges
        assert eng.registry.gauge(
            "wavetpu_solve_model_gbps", "", ("path",)
        ).value(path="roll") > 0.0


class TestDeviceMemory:
    def teardown_method(self):
        perf.set_memory_stats_provider(None)
        perf.configure_memory_warn(None)

    def test_cpu_backend_is_none_and_cached(self):
        # jaxlib's CPU device answers memory_stats() with None -> the
        # whole memory surface reports None and later calls short-
        # circuit on the cached verdict.
        perf.set_memory_stats_provider(None)
        import jax  # noqa: F401  (memory_snapshot consults sys.modules)

        snap = perf.memory_snapshot()
        if snap is not None:  # a backend WITH memory_stats: ints
            assert snap["bytes_in_use"] >= 0
            return
        assert perf.record_memory(MetricsRegistry()) is None

    def test_gauges_watermark_and_warn(self, tmp_path):
        stats = {"bytes_in_use": 1000, "peak_bytes_in_use": 1500}
        perf.set_memory_stats_provider(lambda: dict(stats))
        perf.configure_memory_warn(1200)
        reg = MetricsRegistry()
        tracer_path = str(tmp_path / "trace.jsonl")
        tracing.configure(tracer_path)
        try:
            snap = perf.record_memory(reg, context="solve")
            assert snap == {"bytes_in_use": 1000, "peak_bytes": 1500}
            assert reg.gauge(
                "wavetpu_device_bytes_in_use", "", ("context",)
            ).value(context="solve") == 1000
            assert reg.gauge(
                "wavetpu_device_memory_watermark_bytes", ""
            ).value() == 1000
            raises = reg.counter(
                "wavetpu_device_memory_watermark_raises_total", ""
            )
            assert raises.value() == 1
            # a lower sample never lowers the watermark
            stats["bytes_in_use"] = 800
            perf.record_memory(reg, context="supervisor")
            assert reg.gauge(
                "wavetpu_device_memory_watermark_bytes", ""
            ).value() == 1000
            assert raises.value() == 1
            assert reg.counter(
                "wavetpu_device_memory_warn_total", ""
            ).value() == 0
            # crossing the warn threshold: counter + trace event
            stats["bytes_in_use"] = 2000
            perf.record_memory(reg, context="serve")
            assert reg.counter(
                "wavetpu_device_memory_warn_total", ""
            ).value() == 1
            assert reg.gauge(
                "wavetpu_device_memory_watermark_bytes", ""
            ).value() == 2000
            assert raises.value() == 2
        finally:
            tracing.disable()
        events = [
            json.loads(line) for line in open(tracer_path)
        ]
        warn = [e for e in events if e.get("kind") == "memory.warn"]
        assert len(warn) == 1
        assert warn[0]["attrs"]["bytes_in_use"] == 2000
        assert warn[0]["attrs"]["warn_bytes"] == 1200

    def test_transient_read_failure_does_not_latch_unsupported(self):
        """One failed memory_stats() read (backend bring-up race) must
        NOT permanently disable memory observability: no verdict is
        cached, and the next successful read reports normally."""
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("transient")
            return {"bytes_in_use": 7, "peak_bytes_in_use": 9}

        perf.set_memory_stats_provider(flaky)
        assert perf.memory_snapshot() is None  # the transient failure
        assert perf.memory_snapshot() == {
            "bytes_in_use": 7, "peak_bytes": 9,
        }
        assert calls["n"] == 2  # second call really re-probed

    def test_env_warn_threshold(self, monkeypatch):
        monkeypatch.setenv("WAVETPU_MEM_WARN_BYTES", "4096")
        assert perf.memory_warn_bytes() == 4096
        monkeypatch.setenv("WAVETPU_MEM_WARN_BYTES", "junk")
        assert perf.memory_warn_bytes() is None


class TestProfileSubcommand:
    def test_profile_brackets_a_solve(self, tmp_path, capsys):
        """`wavetpu profile` runs the inner command under jax.profiler,
        injects a telemetry dir so spans annotate the device trace, and
        prints the post-capture summary."""
        from wavetpu import cli

        out = str(tmp_path / "prof")
        rc = cli.main([
            "profile", "--out", out,
            "8", "1", "1", "1", "1", "1", "3",
            "--out-dir", str(tmp_path),
        ])
        assert rc == 0
        printed = capsys.readouterr().out
        assert "profile capture:" in printed
        assert "cli.solve" in printed  # span summary made it
        # the device trace landed
        assert any(
            f.endswith(".xplane.pb")
            for _, _, files in os.walk(out) for f in files
        )
        # and the injected telemetry dir holds the span trace + ledger;
        # the cli.solve span carries the gauge-read roofline attrs
        trace_path = os.path.join(out, "telemetry", "trace.jsonl")
        spans = [json.loads(line) for line in open(trace_path)]
        cs = [s for s in spans if s.get("kind") == "cli.solve"]
        assert cs and cs[-1]["attrs"]["model_gbps"] > 0
        assert cs[-1]["attrs"]["roofline_fraction"] is None  # CPU
        assert os.path.exists(
            os.path.join(out, "telemetry", "compile_ledger.jsonl")
        )

    def test_profile_usage_errors(self, capsys):
        from wavetpu.obs import perf as obs_perf

        assert obs_perf.profile_main([]) == 2
        assert obs_perf.profile_main(["--out", "/tmp/x"]) == 2
        assert obs_perf.profile_main(
            ["--out", "/tmp/x", "8", "--profile", "d"]
        ) == 2
        capsys.readouterr()
