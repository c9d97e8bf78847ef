"""Helpers of the benchmark's CPU tests: a throw-away checkout with tiny
cells added as files, the way a later PR adds a cell."""

from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

TINY = {"tiny": dict(shape=[16, 16, 128], per_chip=[16, 16, 128], chips=1,
                     mesh=None),
        "tiny-x4": dict(shape=[32, 16, 128], per_chip=[8, 16, 128], chips=4,
                        mesh={"shape": [4], "axes": ["i"]})}
TINY_CELLS = [("tiny.apply", "tiny", "apply"),
              ("tiny.smooth4", "tiny", "smooth4"),
              ("tiny.smooth2", "tiny", "smooth2"),
              ("tiny-x4.apply", "tiny-x4", "apply")]
X4_CELLS = [("j27-f32-x4.apply", "j27-f32-x4", "apply")]
# A traffic mix and a per-layer metric, each a new file.
SMOOTH2 = {"name": "smooth2",
           "entry": {"single": "stencil_sweep_driver",
                     "sharded": "stencil_sharded"},
           "sweeps": 2, "loop": "closed", "callers": 1, "why": "test"}
CALLS_READER = '''
def read(trace, record):
    return len(trace.calls()) or None
'''


def make_tiny_root(dest: Path) -> Path:
    """A copy of the benchmark under ``dest`` with tiny configurations, a
    traffic mix, a per-layer metric and cells added as new files and
    entries, and the four-chip cell added as entries; no existing file is
    edited."""
    shutil.copytree(BENCH, dest / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    base = json.loads((BENCH / "configs" / "j27-f32.json").read_text())
    for name, sizes in TINY.items():
        cfg = {**base, **sizes, "name": name}
        (dest / "bench" / "configs" / f"{name}.json").write_text(
            json.dumps(cfg))
        bench["configs"].append({"name": name, "source": "test",
                                 "file": f"bench/configs/{name}.json",
                                 "reduced": [], "why": "test"})
    (dest / "bench" / "traffic" / "smooth2.json").write_text(
        json.dumps(SMOOTH2))
    (dest / "bench" / "metrics" / "calls_traced.py").write_text(CALLS_READER)
    bench["per_layer"].append({
        "name": "calls_traced", "unit": "calls", "better": "higher",
        "source": "program_span", "layer": "entry points",
        "moves": "gstencil_per_s", "workloads": []})
    # The four-chip configuration and the exchange metric, as a later PR
    # would add them: entries only, the files are there.
    bench["configs"].append({"name": "j27-f32-x4", "source": "test",
                             "file": "bench/configs/j27-f32-x4.json",
                             "reduced": [], "why": "test"})
    bench["per_layer"].append({
        "name": "exchange_exposed_pct", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "sharding",
        "moves": "gstencil_per_s", "workloads": []})
    for cell, config, traffic in TINY_CELLS + X4_CELLS:
        chips = 4 if "x4" in config else 1
        bench["workloads"].append({"name": cell, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "test"})
        for m in bench["per_layer"]:
            if m["name"] != "exchange_exposed_pct" or chips > 1:
                m["workloads"].append(cell)
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest
