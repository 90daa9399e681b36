"""Cells are made of files found by name; the result line's keys."""

import json
import os
import shutil

import pytest

import run

ROOT = run.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_every_cell_resolves_by_name(workload):
    cell = run.load_cell(workload)
    assert cell.config["problem"]["N"] > 0
    run.load_module("drivers", cell.traffic["driver"])
    for m in cell.per_layer:
        assert callable(run.load_module("metrics", m["name"]).read)
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    # every per-layer metric's `moves` is an end-to-end metric of the cell
    names = {m["name"] for m in cell.end_to_end}
    assert all(m["moves"] in names for m in cell.per_layer)


def test_every_file_is_used():
    """Each configuration, traffic mix, metric and limits file is named
    by BENCHMARK.json (a file nobody names is dead yardstick)."""
    here = os.path.join(ROOT, "benchmark")
    named = {
        "configs": {os.path.basename(c["file"]) for c in BENCH["configs"]},
        "traffic": {w["traffic"] + ".json" for w in BENCH["workloads"]},
        "limits": {w + ".json" for w in CELLS},
        "metrics": {m["name"] + ".py" for m in BENCH["per_layer"]},
    }
    for kind, want in named.items():
        assert set(os.listdir(os.path.join(here, kind))) - {"__pycache__"} == want


def test_new_mix_is_a_file_only(tmp_path):
    """A later PR adds a cell with a new traffic mix by adding files and
    BENCHMARK.json entries; no existing file of benchmark/ changes."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "ref256-std-serve-open-r2", "config": "ref256",
                               "traffic": "serve-std-open-r2", "chips": 1,
                               "why": "a lower rate"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "ref256-std-serve-open" in m.get("workloads", []):
            m["workloads"].append("ref256-std-serve-open-r2")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    mix = json.load(open(os.path.join(ROOT, "benchmark/traffic/serve-std-open.json")))
    (root / "benchmark/traffic/serve-std-open-r2.json").write_text(
        json.dumps(dict(mix, rate_per_s=2.0)))
    (root / "benchmark/limits/ref256-std-serve-open-r2.json").write_text(
        (root / "benchmark/limits/ref256-std-serve-open.json").read_text())
    cell = run.load_cell("ref256-std-serve-open-r2", root=str(root))
    assert cell.traffic["rate_per_s"] == 2.0 and cell.traffic["driver"] == "serve_open"
    assert [m["name"] for m in cell.per_layer] == [
        m["name"] for m in run.load_cell("ref256-std-serve-open").per_layer]
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_result_line_keys_and_order():
    line = run.result_line(
        True, 12, 0, {"setup_s": {"value": 9.5, "unit": "s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 1},
        {"u_gap": {"value": 1e-7, "limit": 1e-6}})
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "checks"]
    traced = run.result_line(True, 1, 0, {}, {}, {}, {"device_ops": [],
                                                      "idle_gaps": []})
    assert list(traced)[-2:] == ["breakdown", "checks"]
    json.dumps(traced)


def test_judge():
    assert run.judge({"a": 1.0}, {"a": 1.0})
    assert not run.judge({"a": 1.5}, {"a": 1.0})
    assert not run.judge({"a": float("nan")}, {"a": 1.0})
    with pytest.raises(run.BenchError):
        run.judge({"b": 0.0}, {"a": 1.0})


def test_no_tpu_no_result(capsys):
    """Off the TPU a run fails and prints no result line."""
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    out = capsys.readouterr()
    assert rc != 0 and out.out == ""
    assert "needs a TPU" in out.err


def test_bare_checkout_no_result(tmp_path):
    """With only BENCHMARK.json and benchmark/, there is no system under
    test: the run exits non-zero and prints nothing on stdout."""
    import subprocess
    import sys

    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode != 0 and p.stdout == ""
    assert "wavetpu is not in this checkout" in p.stderr
