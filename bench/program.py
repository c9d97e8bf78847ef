"""The program's side of a JAX profiler trace: its spans, the device clock
put on the host's, and the device's idle time put down to the host.

``trace.py`` keeps the device's ops and the harness's own spans.  This
module reads, from the same ``.xplane.pb``, with JAX's
:class:`jax.profiler.ProfileData` alone:

* the host spans of the harness (``bench.*``) and of the engine
  (``stencil.*``, with their arguments: the decision each records);
* per device, each run of a program: the device's ``XLA Modules`` event
  and the host's ``DoEnqueueProgram`` and ``CompleteCallbacks`` events of
  the same ``run_id`` and device ordinal.

**Alignment.**  A run cannot start on the device before the host's enqueue
of it returns, and cannot end after the host sees it complete.  So over a
window's runs the host-minus-device offset lies in [max(enqueue end -
module start), min(completion start - module end)], and its midpoint puts
device times on the host clock.  An empty interval (lo > hi), or no run
paired, is no alignment: what needs one reads nothing.

**Host activity.**  The harness's and the engine's spans run on one thread
and nest, so at each instant the innermost open span says what the host
was doing; ``harness`` stands for time under none (the loop between
calls).  An idle gap of the device is labelled by the activity that holds
most of it on the aligned clock.

A harness :class:`trace.Trace` does not keep the program's spans (it drops
every host event but its own), so :func:`of` finds the program's side of a
``Trace`` by the trace file ``run.py`` wrote for it.
"""

from __future__ import annotations

import bisect
import gzip
import importlib.util
import math
import statistics
import sys
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

ENTRIES = ("stencil.apply", "stencil.sweep_driver", "stencil.sharded")
SELECT, LAUNCH = "stencil.select", "stencil.launch"
PREFIXES = ("bench.", "stencil.")
HARNESS = "harness"          # host time under no span
MODULES, ENQUEUE, COMPLETE = ("XLA Modules", "DoEnqueueProgram",
                              "CompleteCallbacks")
# run.py's traced runs write under tempfile.mkdtemp(prefix="bench-trace-").
TRACE_DIRS = "bench-trace-*"


def _sibling(stem: str):
    """``<stem>.py`` beside this file, loaded once, under the name
    ``run.load_module`` gives it."""
    path = Path(__file__).resolve().parent / f"{stem}.py"
    name = f"bench_{path.parent.name}_{stem}".replace("-", "_")
    mod = sys.modules.get(name)
    if mod is None or Path(mod.__file__) != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return mod


trace_mod = _sibling("trace")    # the interval helpers: merge, ...


@dataclass(frozen=True)
class Span:
    name: str
    start: float          # ns, host clock
    end: float
    args: tuple = ()      # sorted (key, value) pairs


@dataclass(frozen=True)
class Run:
    """One run of a program on a device: the module's span on the device's
    clock, and when the host's enqueue ended and its completion began."""
    start: float
    end: float
    enqueued: float
    completed: float


@dataclass
class Program:
    spans: list = field(default_factory=list)    # Span, by start
    runs: dict = field(default_factory=dict)     # "TPU:0" -> [Run]

    # -- alignment --------------------------------------------------------

    def offset_interval(self, device: str, lo: float, hi: float):
        """(lo, hi) in ns of the host-minus-device offset that the runs
        enqueued inside the host window [lo, hi] allow, or ``None`` when
        none pairs or the interval is empty."""
        runs = [r for r in self.runs.get(device, ())
                if lo <= r.enqueued <= hi]
        if not runs:
            return None
        a = max(r.enqueued - r.start for r in runs)
        b = min(r.completed - r.end for r in runs)
        return (a, b) if a <= b else None

    def offset(self, device: str, lo: float, hi: float):
        """The interval's midpoint in ns, or ``None``."""
        ab = self.offset_interval(device, lo, hi)
        return None if ab is None else (ab[0] + ab[1]) / 2

    # -- spans ------------------------------------------------------------

    def entries(self, lo: float, hi: float) -> list:
        """The outermost entry spans (the calls into the engine) that start
        inside [lo, hi]."""
        out, end = [], -math.inf
        for s in self.spans:
            if s.name in ENTRIES and s.start >= end:
                end = s.end
                if lo <= s.start <= hi:
                    out.append(s)
        return out

    def per_call_ms(self, name: str, lo: float, hi: float):
        """The median over the window's calls of the time in which a
        ``name`` span runs inside each, in ms; ``None`` without calls or
        without such spans."""
        calls = self.entries(lo, hi)
        named = [s for s in self.spans if s.name == name]
        if not calls or not named:
            return None
        starts = [s.start for s in named]
        per_call = []
        for c in calls:
            i = bisect.bisect_left(starts, c.start)
            j = bisect.bisect_left(starts, c.end)
            mine = [s for s in named[i:j] if s.end <= c.end]
            per_call.append(trace_mod.total(trace_mod.merge(mine)) / 1e6)
        return statistics.median(per_call)

    def host_spans(self, lo: float, hi: float) -> dict:
        """``{name: [count, seconds]}`` of the engine's spans that start
        inside [lo, hi]; ``stencil.trace``'s count is the retraces."""
        acc = defaultdict(lambda: [0, 0.0])
        for s in self.spans:
            if s.name.startswith("stencil.") and lo <= s.start <= hi:
                acc[s.name][0] += 1
                acc[s.name][1] += (s.end - s.start) / 1e9
        return dict(sorted(acc.items()))

    def activity(self) -> list:
        """Sorted disjoint ``(start, end, name)``: the host's time, each
        piece named by the innermost span open then (none where no span
        is)."""
        out, stack, t = [], [], -math.inf

        def close(x: float) -> None:
            nonlocal t
            while stack and stack[-1].end <= x:
                top = stack.pop()
                if top.end > t:
                    out.append((t, top.end, top.name))
                    t = top.end
            if stack and x > t:
                out.append((t, x, stack[-1].name))
            t = max(t, x)

        for s in sorted(self.spans, key=lambda s: (s.start, -s.end)):
            close(s.start)
            stack.append(s)
        close(math.inf)
        return out


def split(pieces, ends, s: float, e: float) -> dict:
    """``{activity: ns}`` of the host stretch [s, e] (``harness`` for time
    under no span); ``pieces`` is :meth:`Program.activity`'s list, ``ends``
    its ends."""
    acc = defaultdict(float)
    for ps, pe, name in pieces[bisect.bisect_right(ends, s):]:
        if ps >= e:
            break
        acc[name] += min(pe, e) - max(ps, s)
    covered = sum(acc.values())
    if e - s > covered:
        acc[HARNESS] += e - s - covered
    return acc


# ---------------------------------------------------------------------------
# What the readers and the breakdown read from a harness trace.
# ---------------------------------------------------------------------------

def idle_host(trace, prog: Program, device: str):
    """The device's idle stretches in the harness window on the host clock,
    sorted and disjoint, or ``None`` without an alignment."""
    lo, hi = trace.window()
    off = prog.offset(device, lo, hi)
    if off is None:
        return None
    busy = trace_mod.merge(trace.ops(device, lo - off, hi - off))
    return [(s + off, e + off)
            for s, e in trace_mod.complement(busy, lo - off, hi - off)]


def idle_in_program_pct(trace, prog: Program):
    """The share of each device's idle time in the window during which the
    host is inside an outermost entry span, in %, mean over devices;
    ``None`` without devices, calls, or an alignment of every device."""
    lo, hi = trace.window()
    calls = [(c.start, c.end) for c in prog.entries(lo, hi)]
    if not trace.devices or not calls:
        return None
    shares = []
    for dev in trace.devices:
        idle = idle_host(trace, prog, dev)
        if idle is None:
            return None
        total = trace_mod.total(idle)
        if total > 0:
            outside = trace_mod.total(trace_mod.subtract(idle, calls))
            shares.append(100.0 * (total - outside) / total)
    return sum(shares) / len(shares) if shares else None


def idle_by_host(trace, prog: Program) -> dict:
    """``{activity: seconds}`` of the devices' idle time in the window, each
    stretch put down to the host's activity then, mean over devices;
    ``{}`` without an alignment."""
    acc = defaultdict(float)
    pieces = prog.activity()
    ends = [p[1] for p in pieces]
    for dev in trace.devices:
        idle = idle_host(trace, prog, dev)
        if idle is None:
            return {}
        for s, e in idle:
            for name, ns in split(pieces, ends, s, e).items():
                acc[name] += ns / 1e9 / len(trace.devices)
    return dict(sorted(acc.items(), key=lambda kv: -kv[1]))


def idle_gaps(trace, prog: Program, n: int) -> list:
    """``trace.idle_gaps(n)``, each label followed by ``[host: <activity>]``:
    the activity that holds most of the gap on the aligned clock (``[host:
    unaligned]`` for a device with no alignment)."""
    lo, hi = trace.window()
    gaps = []                    # the same gaps, in trace.idle_gaps' order
    for dev in trace.devices:
        busy = trace_mod.merge(trace.ops(dev, lo, hi))
        gaps += [(e - s, dev, s, e)
                 for s, e in trace_mod.complement(busy, lo, hi)]
    gaps.sort(key=lambda g: -g[0])
    pieces = prog.activity()
    ends = [p[1] for p in pieces]
    out = []
    for (label, secs), (_, dev, s, e) in zip(trace.idle_gaps(n), gaps):
        off = prog.offset(dev, lo, hi)
        host = "unaligned"
        if off is not None:
            host = max(split(pieces, ends, s + off, e + off).items(),
                       key=lambda kv: kv[1])[0]
        out.append([f"{label} [host: {host}]", secs])
    return out


# ---------------------------------------------------------------------------
# Reading.
# ---------------------------------------------------------------------------

def from_profile(profile) -> Program:
    """A :class:`Program` from a :class:`jax.profiler.ProfileData`."""
    prog = Program()
    modules, enqueued, completed = {}, {}, {}
    for plane in profile.planes:
        m = trace_mod.DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == MODULES:
                    for e in line.events:
                        run_id = dict(e.stats).get("run_id")
                        modules[(int(m.group(1)), run_id)] = (
                            e.start_ns, e.start_ns + e.duration_ns)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    end = e.start_ns + e.duration_ns
                    if e.name.startswith(PREFIXES):
                        prog.spans.append(Span(e.name, e.start_ns, end,
                                               tuple(sorted(e.stats))))
                    elif e.name in (ENQUEUE, COMPLETE):
                        st = dict(e.stats)
                        key = (st.get("device_ordinal"), st.get("run_id"))
                        # Of several events for one run: the earliest
                        # enqueue end (the weakest bound) and the earliest
                        # completion (each one follows the run's end).
                        if e.name == ENQUEUE:
                            enqueued[key] = min(enqueued.get(key, end), end)
                        else:
                            completed[key] = min(
                                completed.get(key, e.start_ns), e.start_ns)
    for (ordinal, run_id), (s, e) in sorted(modules.items(),
                                           key=lambda kv: kv[1]):
        key = (ordinal, run_id)
        if key in enqueued and key in completed:
            prog.runs.setdefault(f"TPU:{ordinal}", []).append(
                Run(s, e, enqueued[key], completed[key]))
    prog.spans.sort(key=lambda s: (s.start, -s.end))
    return prog


def load(path) -> Program:
    """Read a ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    return from_profile(ProfileData.from_serialized_xspace(data))


def of(trace):
    """The program's side of the harness trace ``trace``, or ``None``.

    ``trace.program`` where the trace carries it; else the newest trace
    file under ``run.py``'s temporary trace directories whose ``bench.call``
    spans are exactly ``trace``'s (the file ``run.py`` is reading), whose
    offset interval is then printed once as an ``info:`` line.  A trace
    with no device plane (a run off the chip) reads ``None``."""
    if "program" in vars(trace):
        return trace.program
    trace.program = None
    calls = [(c.start, c.end) for c in trace.calls()]
    if not trace.devices or not calls:
        return None
    files = sorted(Path(tempfile.gettempdir()).glob(
        f"{TRACE_DIRS}/**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    for path in reversed(files[-3:]):
        try:
            prog = load(path)
        except (OSError, RuntimeError):   # another run's, cut off or gone
            continue
        if [(s.start, s.end) for s in prog.spans
                if s.name == "bench.call"] == calls:
            trace.program = prog
            lo, hi = trace.window()
            for dev in trace.devices:
                ab = prog.offset_interval(dev, lo, hi)
                shown = ("none" if ab is None else
                         f"[{ab[0] / 1e6!r}, {ab[1] / 1e6!r}] ms")
                print(f"info: host-minus-device clock offset on {dev}: "
                      f"{shown}", flush=True)
            break
    return trace.program
