"""The host time of the engine's executor calls, per call, in ms: the
median over the window's calls (its outermost ``stencil.*`` entry spans)
of the time its ``stencil.launch`` spans (JAX's dispatch and the runtime's
enqueue) run inside each.  Read from the program's own spans
(``program.py``); nothing to read where the program has none, or the trace
has no device."""

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
    return None if prog is None else prog.per_call_ms(program.LAUNCH,
                                                      *trace.window())
