"""The share of the device's idle time in the window during which the host
is inside a call into the engine (an outermost ``stencil.*`` entry span),
in %, mean over devices: the idle time the program's own host code can
shorten.  The device's idle stretches go onto the host clock by the
run_id alignment of ``program.py``; nothing to read without it, without
the program's spans, or without a device."""

import importlib.util
import sys
from pathlib import Path


def _program():
    path = Path(__file__).resolve().parents[1] / "program.py"
    name = f"bench_{path.parent.name}_program"
    mod = sys.modules.get(name)
    if mod is None or Path(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod       # dataclasses look their module up
        spec.loader.exec_module(mod)
    return mod


def read(trace, record):
    program = _program()
    prog = program.of(trace)
    return None if prog is None else program.idle_in_program_pct(trace, prog)
