"""The call's share of the chip's roofline, in %.

The least time of one call on one chip (the needed bytes over peak HBM
bandwidth; see ``work.py``), times the calls traced, over the device's busy
time in the window: the union of every device op the calls launched, Pallas
kernel, XLA fusion, copy or collective alike.  Mean over the cell's
devices.
"""

import importlib.util
from pathlib import Path


def _work():
    path = Path(__file__).resolve().parent.parent / "work.py"
    spec = importlib.util.spec_from_file_location("bench_work", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(trace, record):
    if not trace.devices:
        return None
    lo, hi = trace.window()
    least = _work().least_seconds(record["config"], record["device_kind"])
    calls = len(trace.calls())
    shares = []
    for dev in trace.devices:
        busy = trace.busy_ns(dev, lo, hi) / 1e9
        if busy > 0:
            shares.append(100.0 * least * calls / busy)
    return sum(shares) / len(shares) if shares else None
