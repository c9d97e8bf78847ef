#!/usr/bin/env python3
"""What the host was doing while the device sat idle, from one trace.

    python3 bench/explain.py --workload <cell> --seed <n> --seconds <s>
                             [--keep <dir>]
    python3 bench/explain.py --file <trace.xplane.pb[.gz]>

The first makes one ``--trace 1`` run of the cell as ``run.py`` does (it
needs the chip), prints the run's result line and keeps a copy of the
trace under ``--keep``; the second reads a trace kept before.  Either then
prints one JSON object read from the program's spans (``program.py``):

* ``offset_ms``: per device, the host-minus-device clock offset interval
  that the run_id pairs allow;
* ``host_spans``: count and seconds of each ``stencil.*`` span in the
  window (``stencil.trace``'s count is the retraces inside it);
* ``per_call_ms``: the median per call of the time in each engine span;
* ``idle_by_host_s``: the devices' idle time split by what the host was
  doing (the innermost span open, ``harness`` under none);
* ``idle_gaps``: the ten longest idle gaps, each with its ``[host: ...]``.

The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import shutil
import sys
import tempfile
from pathlib import Path

import run

program = run.load_module(run.BENCH / "program.py")
trace_mod = run.load_module(run.BENCH / "trace.py")


def explain(path) -> dict:
    """The program's side of the trace at ``path``, as set out above."""
    tr = trace_mod.load(path)
    prog = program.load(path)
    lo, hi = tr.window()
    offsets = {}
    for dev in tr.devices:
        ab = prog.offset_interval(dev, lo, hi)
        offsets[dev] = None if ab is None else [ab[0] / 1e6, ab[1] / 1e6]
    names = sorted({s.name for s in prog.spans
                    if s.name.startswith("stencil.")})
    return {"offset_ms": offsets,
            "host_spans": prog.host_spans(lo, hi),
            "per_call_ms": {n: prog.per_call_ms(n, lo, hi) for n in names},
            "idle_by_host_s": program.idle_by_host(tr, prog),
            "idle_gaps": program.idle_gaps(tr, prog, 10)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--file")
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--keep", type=Path)
    args = ap.parse_args(argv)
    if args.file:
        print(json.dumps(explain(args.file)), flush=True)
        return 0
    if not (args.workload and args.seed is not None):
        ap.error("give --file, or --workload and --seed")
    sys.path.insert(0, str(run.ROOT / "src"))
    cell = run.find_cell(run.ROOT, args.workload)
    try:
        devices = run.tpu_devices(cell.chips)
    except run.NoChip as e:
        print(f"explain: no chip: {e}", file=sys.stderr)
        return 2
    import jax
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # Where run.py writes its own, so that the readers find the trace.
    tmp = Path(tempfile.mkdtemp(prefix="bench-trace-"))
    try:
        result = run.run_cell(run.ROOT, args.workload, args.seed,
                              args.seconds, True, devices, trace_dir=tmp)
        print(json.dumps(result), flush=True)
        path = sorted(tmp.glob("**/*.xplane.pb"))[-1]
        print(json.dumps(explain(path)), flush=True)
        if args.keep:
            args.keep.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, args.keep / f"{args.workload}.xplane.pb")
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
