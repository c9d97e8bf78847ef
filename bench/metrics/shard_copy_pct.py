"""The share of each device's busy time in which an op runs that is neither
an engine kernel nor a collective, in %, mean over devices.

On the sharded path these are the shard body's own copies: the
concatenation of the exchanged halo planes onto the local slab and the crop
of the kernel's output back to the local planes.  An engine kernel is a
custom call whose name starts with ``stencil_``, the prefix every kernel
name of the engine carries (the trace carries no ``op_name`` metadata, so a
named scope would not reach it); a collective is told by its opcode, as in
``exchange_exposed_pct``.  Nothing to read, and no number, where the trace
holds no device.
"""

import importlib.util
import sys
from pathlib import Path

# The engine names each ``pallas_call`` ``stencil_<kind>``; XLA suffixes
# the instruction's name (``stencil_stream_jtiled.1``).
KERNEL_PREFIX = "stencil_"


def _exchange():
    path = Path(__file__).resolve().with_name("exchange_exposed_pct.py")
    name = f"bench_{path.parent.name}_{path.stem}"
    mod = sys.modules.get(name)
    if mod is None or Path(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


_is_collective = _exchange().is_collective


def is_kernel(op) -> bool:
    return op.kind == "custom-call" and op.name.startswith(KERNEL_PREFIX)


def is_copy(op) -> bool:
    """Neither an engine kernel nor a collective."""
    return not (is_kernel(op) or _is_collective(op))


def read(trace, record):
    if not trace.devices:
        return None
    lo, hi = trace.window()
    shares = []
    for dev in trace.devices:
        busy = trace.busy_ns(dev, lo, hi)
        if busy > 0:
            copy, _ = trace.exposed_ns(dev, lo, hi, is_copy)
            shares.append(100.0 * copy / busy)
    return sum(shares) / len(shares) if shares else None
