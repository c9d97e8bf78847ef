"""Benchmark harness: one module per paper table + system benches.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).  A
sub-benchmark that raises is reported as a ``FAILED`` row and the process
exits non-zero -- a crashed run can't green-wash the CI bench step.  Each
sub-benchmark also runs under a wall-clock timeout (``BENCH_TIMEOUT_S``
seconds, default 900) so a hung benchmark produces a FAILED row and exit 1
instead of stalling CI until the job-level kill.
"""

from __future__ import annotations

import os
import signal
import sys
import traceback

DEFAULT_TIMEOUT_S = 900


class BenchTimeout(Exception):
    pass


def _timeout_s() -> int:
    try:
        return max(0, int(os.environ.get("BENCH_TIMEOUT_S",
                                         DEFAULT_TIMEOUT_S)))
    except ValueError:
        return DEFAULT_TIMEOUT_S


def _run_rows(name: str, mod, timeout_s: int) -> None:
    """Print the module's rows, raising :class:`BenchTimeout` if the module
    exceeds the wall-clock budget.  SIGALRM-based, so it interrupts a
    genuinely wedged benchmark (not just one that checks a flag); on
    platforms without SIGALRM the benchmark runs unbounded."""
    use_alarm = timeout_s > 0 and hasattr(signal, "SIGALRM")
    if use_alarm:
        def _on_alarm(signum, frame):
            raise BenchTimeout(
                f"{name} exceeded BENCH_TIMEOUT_S={timeout_s}s")
        prev = signal.signal(signal.SIGALRM, _on_alarm)
        signal.alarm(timeout_s)
    try:
        for row in mod.run():
            print(row)
    finally:
        if use_alarm:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, prev)


def main() -> None:
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    from benchmarks import (roofline, stencil_throughput, table1_subkernels,
                            table2_requirements, table3_model_validation)
    mods = [("table1", table1_subkernels), ("table2", table2_requirements),
            ("table3", table3_model_validation),
            ("stencil_throughput", stencil_throughput),
            ("roofline", roofline)]
    only = sys.argv[1] if len(sys.argv) > 1 else None
    if only and only not in [n for n, _ in mods]:
        print(f"unknown benchmark {only!r}; available: "
              f"{[n for n, _ in mods]}", file=sys.stderr)
        sys.exit(2)
    timeout_s = _timeout_s()
    failed = []
    print("name,us_per_call,derived")
    for name, mod in mods:
        if only and only != name:
            continue
        try:
            _run_rows(name, mod, timeout_s)
        except SystemExit:
            raise                      # an explicit gate verdict: keep it
        except BenchTimeout as exc:
            failed.append(name)
            print(f"{name},nan,FAILED: timeout: {exc}")
            print(f"benchmark {name} timed out after {timeout_s}s",
                  file=sys.stderr)
        except Exception as exc:       # noqa: BLE001 - report, then fail
            failed.append(name)
            print(f"{name},nan,FAILED: {type(exc).__name__}: {exc}")
            traceback.print_exc(file=sys.stderr)
    if failed:
        print(f"benchmark failures: {', '.join(failed)}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
