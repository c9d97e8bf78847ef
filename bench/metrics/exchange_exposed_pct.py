"""The share of each device's busy time in which a collective runs and no
compute op runs on that device, in %, mean over devices.  Nothing to read,
and no number, where the trace holds no collective."""

COLLECTIVES = ("all-reduce", "all-gather", "all-to-all", "reduce-scatter",
               "collective-permute", "collective-broadcast", "send", "recv")


def is_collective(op) -> bool:
    """A collective or one of its asynchronous halves, by opcode."""
    return op.kind.startswith(COLLECTIVES)


def read(trace, record):
    lo, hi = trace.window()
    shares, seen = [], False
    for dev in trace.devices:
        coll, exposed = trace.exposed_ns(dev, lo, hi, is_collective)
        seen = seen or coll > 0
        busy = trace.busy_ns(dev, lo, hi)
        if busy > 0:
            shares.append(100.0 * exposed / busy)
    return sum(shares) / len(shares) if seen and shares else None
