#!/usr/bin/env python3
"""The chip benchmark of the stencil engine: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``workloads`` in ``BENCHMARK.json``: a configuration
(``bench/configs/<config>.json``) under a traffic mix
(``bench/traffic/<mix>.json``).  The mix names the entry point, whose driver
is ``bench/entries/<entry>.py``; the configuration names its plain reference,
``bench/references/<name>.py``; each per-layer metric is read by
``bench/metrics/<metric>.py``.  Adding a cell adds files; it edits none.

One run, in one process that holds the cell's chips:

1. JAX must see a TPU and at least the cell's chips, else the run exits 2
   and prints no result.
2. The persistent compilation cache is on (``repro.compile_cache``).
3. The field is made on the device from ``--seed`` (uniform in [-1, 1));
   the weights are the configuration's own.
4. The entry is called twice to compile and warm up: ``setup_s`` runs from
   process start to the window's start.
5. The window: a closed loop with one caller for ``--seconds``, every call
   on the same field, timed by the host clock from just before the entry is
   called until ``block_until_ready`` returns.  A call that raises counts
   as failed.  Outputs of two calls, drawn from the seed over the whole
   window, are kept.
6. Once the window has closed and the peak memory is read, the kept outputs
   are compared with the plain reference: the widest gap, over the largest
   reference magnitude, must stay within the configuration's limit.

``--trace 1`` records a profiler trace of the window and reports the
per-layer metrics instead of the end-to-end ones, plus ``busy_s``,
``window_s`` and a ``breakdown``.  The last line of standard output is one
JSON object; the numbers compared, each beside its limit, are the last
lines of standard error and the last key of that object.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
KEEP = 2                     # outputs kept from the window for the check
SPAN_CALL, SPAN_SYNC, SPAN_CHECK = "bench.call", "bench.sync", "bench.check"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """JAX sees no TPU, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    bench: Path

    @property
    def sweeps(self) -> int:
        return self.traffic["sweeps"]

    @property
    def entry(self) -> str:
        kind = "single" if self.config["mesh"] is None else "sharded"
        return self.traffic["entry"][kind]


def load_module(path: Path):
    """Import one file of the benchmark by path (once per process)."""
    name = f"bench_{path.parent.name}_{path.stem}".replace("-", "_")
    mod = sys.modules.get(name)
    if mod is not None and Path(mod.__file__) == path:
        return mod
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod           # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def find_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its files read."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {root / 'BENCHMARK.json'}; "
                       f"known: {sorted(cells)}")
    w = cells[name]
    files = {c["name"]: c["file"] for c in bench["configs"]}
    config = json.loads((root / files[w["config"]]).read_text())
    bench_dir = (root / files[w["config"]]).parent.parent
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    if config["chips"] != w["chips"]:
        raise ValueError(f"{name}: the cell asks for {w['chips']} chips, its "
                         f"configuration {w['config']} for {config['chips']}")
    return Cell(name, config, traffic, w["chips"], bench_dir)


def metrics_of(root: Path, cell_name: str, kind: str) -> list:
    """The ``kind`` (``end_to_end`` or ``per_layer``) metrics of
    ``BENCHMARK.json`` that cell ``cell_name`` reports."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def tpu_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; :class:`NoChip` when there are none
    or too few.  Never falls back to the CPU."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devs[0].platform!r} "
                     f"({devs[0].device_kind}), not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def info(msg: str) -> None:
    print(f"info: {msg}", flush=True)


# ---------------------------------------------------------------------------
# Set-up: mesh, data, entry.
# ---------------------------------------------------------------------------

def make_mesh(cell: Cell, devices):
    """The configuration's mesh over ``devices``, or ``None`` on one chip."""
    mesh = cell.config["mesh"]
    if mesh is None:
        return None
    from jax.sharding import AxisType, Mesh
    # Auto axes leave the reference's pad and slices to the partitioner.
    return Mesh(np.asarray(devices).reshape(mesh["shape"]),
                tuple(mesh["axes"]),
                axis_types=(AxisType.Auto,) * len(mesh["shape"]))


def placements(cell: Cell, devices, mesh):
    """(field sharding, weights sharding) on the cell's devices."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from jax.sharding import SingleDeviceSharding
    if mesh is None:
        one = SingleDeviceSharding(devices[0])
        return one, one
    return (NamedSharding(mesh, P(*mesh.axis_names)),
            NamedSharding(mesh, P()))


def make_field(cell: Cell, seed: int, sharding):
    """The input field, uniform in [-1, 1), made on the device in one jitted
    call from ``seed`` (any non-negative integer, 64 bits and more)."""
    words = np.random.SeedSequence(seed).generate_state(2, dtype=np.uint32)
    shape = tuple(cell.config["shape"])
    dtype = jnp.dtype(cell.config["dtype"])

    def make(key_data):
        key = jax.random.wrap_key_data(key_data, impl="threefry2x32")
        return jax.random.uniform(key, shape, dtype, -1.0, 1.0)
    return jax.jit(make, out_shardings=sharding)(jnp.asarray(words))


def make_weights(cell: Cell, sharding):
    return jax.device_put(np.asarray(cell.config["weights"], np.float32),
                          sharding)


def make_call(cell: Cell, mesh):
    """The entry's driver: a callable ``(u, w) -> out``."""
    mod = load_module(cell.bench / "entries" / f"{cell.entry}.py")
    return mod.make(cell.config, cell.traffic, mesh), mod


# ---------------------------------------------------------------------------
# The window.
# ---------------------------------------------------------------------------

@dataclass
class Window:
    seconds: float = 0.0          # wall time of the window
    attempted: int = 0
    failed: int = 0
    call_s: list = field(default_factory=list)      # entry to ready, each
    dispatch_s: list = field(default_factory=list)  # entry to return, each
    kept: list = field(default_factory=list)  # [(call index, output)]
    compiles: int = 0             # backend compilations inside the window
    error: str = ""               # the first failure's traceback


def run_window(call, u, w, seconds: float, seed: int) -> Window:
    """A closed loop with one caller on the same field for ``seconds``."""
    rng = np.random.default_rng(seed)
    win = Window()

    def on_compile(event, duration, **_):
        if event == COMPILE_EVENT:
            win.compiles += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        start = time.perf_counter()
        deadline = start + seconds
        end = start
        while end < deadline:
            win.attempted += 1
            t0 = time.perf_counter()
            try:
                with jax.profiler.TraceAnnotation(SPAN_CALL):
                    out = call(u, w)
                t1 = time.perf_counter()
                with jax.profiler.TraceAnnotation(SPAN_SYNC):
                    out.block_until_ready()
            except Exception:            # counted; the window goes on
                win.failed += 1
                win.error = win.error or traceback.format_exc()
                end = time.perf_counter()
                continue
            end = time.perf_counter()
            win.call_s.append(end - t0)
            win.dispatch_s.append(t1 - t0)
            n = len(win.call_s) - 1      # reservoir sample over all calls
            if n < KEEP:
                win.kept.append((n, out))
            else:
                j = int(rng.integers(0, n + 1))
                if j < KEEP:
                    win.kept[j] = (n, out)
            del out
        win.seconds = end - start
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    return win


def percentile(values, q: int) -> float:
    """The ``q``-th percentile (inclusive method of :mod:`statistics`)."""
    if len(values) == 1:
        return values[0]
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]


def end_to_end(cell: Cell, win: Window, setup_s: float) -> dict:
    pts = math.prod(cell.config["shape"])
    done = len(win.call_s)
    out = {"setup_s": {"value": setup_s, "unit": "s"}}
    if done:
        out["gstencil_per_s"] = {
            "value": pts * cell.sweeps * done / win.seconds / 1e9,
            "unit": "Gstencil/s"}
        out["call_ms_p95"] = {
            "value": percentile([t * 1e3 for t in win.call_s], 95),
            "unit": "ms"}
    return out


# ---------------------------------------------------------------------------
# The check.
# ---------------------------------------------------------------------------

@jax.jit
def _gap(a, b):
    return jnp.max(jnp.abs(a.astype(jnp.float32) - b)) / jnp.max(jnp.abs(b))


def max_rel_err(got, want) -> float:
    """The widest gap of ``got`` below or above ``want``, over the largest
    magnitude of ``want``; NaN when ``got`` holds a NaN."""
    if got.shape != want.shape:
        return math.inf
    return float(_gap(got, want))


def reference(cell: Cell, u):
    mod = load_module(cell.bench / "references"
                      / f"{cell.config['reference']}.py")
    return mod.reference(u, cell.config["weights"], cell.config["bc"],
                         cell.sweeps)


def compare(cell: Cell, u, outputs, dtype) -> float:
    """The largest ``max_rel_err`` of ``outputs`` against the reference on
    ``u``; inf when there are none or one is not of ``dtype``."""
    want = reference(cell, u)
    errs = [max_rel_err(o, want) if o.dtype == dtype else math.inf
            for o in outputs]
    if not errs:
        return math.inf
    return math.nan if any(map(math.isnan, errs)) else max(errs)


# ---------------------------------------------------------------------------
# The traced run.
# ---------------------------------------------------------------------------

def trace_options():
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # a span per Python call would swamp
    opts.host_tracer_level = 2        # the host and the trace
    return opts


def read_trace(root: Path, cell: Cell, trace_dir: Path,
               device_kind: str) -> tuple:
    """(per-layer metrics, device record, breakdown) from the trace."""
    trace_mod = load_module(cell.bench / "trace.py")
    files = sorted(trace_dir.glob("**/*.xplane.pb"))
    if not files:
        raise RuntimeError(f"the profiler wrote no trace under {trace_dir}")
    tr = trace_mod.load(files[-1])
    record = {"config": cell.config, "sweeps": cell.sweeps,
              "device_kind": device_kind}
    metrics = {}
    for m in metrics_of(root, cell.name, "per_layer"):
        reader = load_module(cell.bench / "metrics" / f"{m['name']}.py")
        value = reader.read(tr, record)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    lo, hi = tr.window()
    busy = [tr.busy_ns(d, lo, hi) for d in tr.devices]
    device = {"busy_s": (sum(busy) / len(busy) / 1e9) if busy else 0.0,
              "window_s": (hi - lo) / 1e9}
    breakdown = {"device_ops": tr.top_ops(10), "idle_gaps": tr.idle_gaps(10)}
    return metrics, device, breakdown


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

def run_cell(root: Path, name: str, seed: int, seconds: float, trace: bool,
             devices, trace_dir: Path | None = None,
             start: float = _PROCESS_START, field_dtype=None) -> dict:
    """Run cell ``name`` of ``root/BENCHMARK.json`` on ``devices``; return
    the result object (the last line a run prints).

    ``field_dtype`` casts the field before the entry sees it, the reference
    still running on the configuration's own: the lower-precision control
    (``control.py``).  ``trace_dir`` keeps the trace there."""
    cell = find_cell(root, name)
    info(f"cell={name} seed={seed} seconds={seconds} trace={int(trace)} "
         f"entry={cell.entry} sweeps={cell.sweeps} shape={cell.config['shape']}"
         f" dtype={cell.config['dtype']}")
    mesh = make_mesh(cell, devices)
    field_sh, w_sh = placements(cell, devices, mesh)
    t = time.perf_counter()
    u = make_field(cell, seed, field_sh)
    w = make_weights(cell, w_sh)
    x = u if field_dtype is None else u.astype(field_dtype)
    x.block_until_ready()
    info(f"field made in {time.perf_counter() - t!r} s")
    call, entry_mod = make_call(cell, mesh)
    describe = getattr(entry_mod, "describe", None)
    if describe is not None:
        try:
            info(f"engine choice: {describe(cell.config, cell.traffic)}")
        except Exception as e:        # informative only: never fails a run
            info(f"engine choice not readable: {e!r}")
    for i in range(2):                # compile, then one warm call
        t = time.perf_counter()
        call(x, w).block_until_ready()
        info(f"warm-up call {i} took {time.perf_counter() - t!r} s")
    setup_s = time.perf_counter() - start

    tmp = None
    if trace:
        if trace_dir is None:
            tmp = trace_dir = Path(tempfile.mkdtemp(prefix="bench-trace-"))
        jax.profiler.start_trace(str(trace_dir),
                                 profiler_options=trace_options())
    try:
        win = run_window(call, x, w, seconds, seed)
        stats = [d.memory_stats() or {} for d in devices]
        peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
        info(f"window: {len(win.call_s)} calls in {win.seconds!r} s, "
             f"{win.failed} failed, {win.compiles} compilations inside, "
             f"median dispatch "
             f"{statistics.median(win.dispatch_s or [math.nan])!r} s, "
             f"kept calls {[i for i, _ in win.kept]}")
        if win.error:
            print(win.error, file=sys.stderr)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation(SPAN_CHECK):
            outputs = [o for _, o in win.kept]
            win.kept = None
            err = compare(cell, u, outputs, x.dtype)
            del outputs
        info(f"check took {time.perf_counter() - t!r} s")
    finally:
        if trace:
            jax.profiler.stop_trace()
    limit = cell.config["check"]["limit"]
    correct = (win.failed == 0 and len(win.call_s) > 0 and err <= limit)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed}
    if trace:
        try:
            metrics, dev_rec, breakdown = read_trace(root, cell, trace_dir,
                                                     dev.device_kind)
        finally:
            if tmp is not None:
                shutil.rmtree(tmp, ignore_errors=True)
        result["metrics"] = metrics
        result["device"] = {**device, **dev_rec}
        result["breakdown"] = breakdown
    else:
        wanted = {m["name"] for m in metrics_of(root, name, "end_to_end")}
        e2e = end_to_end(cell, win, setup_s)
        result["metrics"] = {k: v for k, v in e2e.items() if k in wanted}
        result["device"] = device
    check = cell.config["check"]
    # JSON has no inf or NaN: a run with no number to compare says null.
    result["checks"] = {check["number"]: {
        "value": err if math.isfinite(err) else None, "limit": limit}}
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"bench: the program is missing ({src / 'repro'} not found)",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    cell = find_cell(ROOT, args.workload)
    try:
        devices = tpu_devices(cell.chips)
    except NoChip as e:
        print(f"bench: no chip: {e}; this benchmark runs on a TPU only",
              file=sys.stderr)
        return 2
    load_module(BENCH / "work.py").peak(devices[0].device_kind)
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    # Every program the run uses, small ones too, comes from the cache after
    # a cell's first run, so set-up stays steady.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    info(f"device kind={devices[0].device_kind!r} count={len(jax.devices())} "
         f"jax={jax.__version__} compile_cache={cache}")
    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      bool(args.trace), devices)
    for number, c in result["checks"].items():
        print(f"check: {number} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
