"""The benchmark harness on the CPU: files found by name, a cell added by
files alone, the work model, the peak table, the refusal without a chip,
and the result line's keys."""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

import run
import work
from bench_tiny import BENCH, ROOT, TINY_CELLS

CELLS = ["j27-f32.apply", "j27-f32.smooth4"]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell,config,mix,entry", [
    ("j27-f32.apply", "j27-f32", "apply", "stencil_apply"),
    ("j27-f32.smooth4", "j27-f32", "smooth4", "stencil_sweep_driver"),
    ("j27-f32-x4.apply", "j27-f32-x4", "apply", "stencil_sharded"),
])
def test_cell_files_found_by_name(tiny_root, cell, config, mix, entry):
    """The benchmark's cells, and the four-chip one that a later PR adds
    as entries over files already here."""
    c = run.find_cell(tiny_root, cell)
    assert (c.config["name"], c.traffic["name"], c.entry) == (config, mix,
                                                             entry)
    assert (BENCH / "entries" / f"{entry}.py").is_file()
    assert callable(run.load_module(BENCH / "entries" / f"{entry}.py").make)
    ref = run.load_module(BENCH / "references"
                          / f"{c.config['reference']}.py")
    assert callable(ref.reference)


@pytest.mark.parametrize("metric", sorted(
    {m["name"] for m in benchmark()["per_layer"]}
    | {p.stem for p in (BENCH / "metrics").glob("*.py")}))
def test_metric_reader_found_by_name(metric):
    reader = run.load_module(BENCH / "metrics" / f"{metric}.py")
    assert callable(reader.read)


def test_benchmark_json_keeps_its_shape():
    b = benchmark()
    assert list(b) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    assert b["command"] == ["python3", "bench/run.py"] and b["paths"] == [
        "bench"]
    configs = {c["name"]: c for c in b["configs"]}
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert len(c["source"]) <= 200 and c["file"].startswith("bench/")
        assert json.loads((ROOT / c["file"]).read_text())["source"] == \
            c["source"]
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and len(w["why"]) <= 200
        assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] == "host_clock"
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= {w["name"] for w in b["workloads"]}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in b[k]]
    assert len(names) == len(set(names)) and all(map(NAME.match, names))
    assert [w["name"] for w in b["workloads"]] == CELLS


def test_needed_bytes_per_chip():
    for name in ("j27-f32", "j27-f32-x4"):
        cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
        assert work.needed_bytes(cfg) == 2_147_483_648
    x4 = json.loads((BENCH / "configs" / "j27-f32-x4.json").read_text())
    assert work.points(x4) == 4 * work.points_per_chip(x4)


def test_peaks_refuse_an_unknown_device_kind():
    assert work.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        work.peak("TPU v9 imaginary")
    with pytest.raises(KeyError):
        work.least_seconds(json.loads(
            (BENCH / "configs" / "j27-f32.json").read_text()), "cpu")


@pytest.mark.parametrize("name", ["j27-f32", "j27-f32-x4"])
def test_least_time_is_the_needed_bytes_at_peak_bandwidth(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert work.least_seconds(cfg, "TPU v5 lite") == 2**31 / 819e9


def _command(cwd: Path, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "j27-f32.apply",
         "--seed", str(2**33 + 5), "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    return any(line.startswith("{") for line in stdout.splitlines())


def test_run_refuses_without_a_tpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "no chip" in proc.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    proc = _command(tmp_path)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", [c for c, _, _ in TINY_CELLS
                                  if "x4" not in c])
def test_a_cell_added_by_files_runs(tiny_root, cell, trace):
    """A throw-away configuration under the existing mixes becomes a
    runnable cell with new files and entries only; the run (CPU, Pallas in
    interpret mode; only the chip check is skipped) is correct and its
    result line has exactly the contract's keys."""
    res = run.run_cell(tiny_root, cell, 2**40 + 3, 0.2, trace,
                       jax.devices()[:1])
    extra = ["breakdown"] if trace else []
    assert list(res) == RESULT_KEYS + extra + ["checks"]
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    if trace:
        assert set(res["device"]) >= {"busy_s", "window_s"}
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
        # No device plane on the CPU: only the host-span readers read, the
        # one added as a file among them.
        assert set(res["metrics"]) == {"dispatch_ms", "calls_traced"}
        assert res["metrics"]["calls_traced"]["value"] == res["attempted"]
    else:
        assert set(res["metrics"]) == {"gstencil_per_s", "call_ms_p95",
                                       "setup_s"}
        assert all(m["value"] > 0 for m in res["metrics"].values())
    (number, c), = res["checks"].items()
    assert number == "max_rel_err" and c["value"] <= c["limit"]
    json.dumps(res, allow_nan=False)


def test_same_seed_same_field():
    cell = run.find_cell(ROOT, "j27-f32.apply")
    cell.config = {**cell.config, "shape": [4, 8, 128]}
    sh = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    a = run.make_field(cell, 2**40 + 1, sh)
    b = run.make_field(cell, 2**40 + 1, sh)
    c = run.make_field(cell, 2**40 + 2, sh)
    assert (a == b).all() and not (a == c).all()
    assert float(a.min()) >= -1.0 and float(a.max()) < 1.0
