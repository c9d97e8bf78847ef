"""The share of the traced window in which no op runs on the device, in %:
1 - (union of the device's op intervals / window), mean over devices."""


def read(trace, record):
    if not trace.devices:
        return None
    lo, hi = trace.window()
    idle = [100.0 * (1.0 - trace.busy_ns(d, lo, hi) / (hi - lo))
            for d in trace.devices]
    return sum(idle) / len(idle)
