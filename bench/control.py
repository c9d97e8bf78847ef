#!/usr/bin/env python3
"""The readings a cell's limit is set from: the program and its control.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... [--seconds 1]

For each seed, in one process on the chip: a short run of the cell as the
benchmark makes it (the program's reading of the number compared), then the
same run with the field cast to the configuration's ``control_dtype`` before
the entry sees it (the program's own lower-precision path), compared with
the same full-precision reference.  The control has to read above the
limit, every sound run below it.  One JSON line per seed, then a summary.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def readings(root, name: str, seeds, seconds: float, devices) -> list:
    """``[{"seed", "program", "control", "correct"}]`` for each seed."""
    cell = run.find_cell(root, name)
    ctrl = cell.config["check"]["control_dtype"]
    number = cell.config["check"]["number"]
    rows = []
    for seed in seeds:
        prog = run.run_cell(root, name, seed, seconds, False, devices)
        low = run.run_cell(root, name, seed, seconds, False, devices,
                           field_dtype=ctrl)
        rows.append({"seed": seed,
                     "program": prog["checks"][number]["value"],
                     "control": low["checks"][number]["value"],
                     "program_correct": prog["correct"],
                     "control_correct": low["correct"]})
        print(json.dumps(rows[-1]), flush=True)
    return rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(run.ROOT / "src"))
    cell = run.find_cell(run.ROOT, args.workload)
    try:
        devices = run.tpu_devices(cell.chips)
    except run.NoChip as e:
        print(f"control: no chip: {e}", file=sys.stderr)
        return 2
    from repro.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    rows = readings(run.ROOT, args.workload, args.seeds, args.seconds,
                    devices)
    # null marks a run with no number: a crash or a NaN, which has failed
    # and sets no end of the range.
    prog = [r["program"] for r in rows if r["program"] is not None]
    ctrl = [r["control"] for r in rows if r["control"] is not None]
    print(json.dumps({"workload": args.workload,
                      "limit": cell.config["check"]["limit"],
                      "program_max": max(prog, default=None),
                      "control_min": min(ctrl, default=None),
                      "seeds": args.seeds}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
