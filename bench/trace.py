"""Reduce a JAX profiler trace to device-op intervals and the harness's spans.

:func:`load` reads the ``.xplane.pb`` the profiler writes (or a gzipped
copy) with nothing but JAX's :class:`jax.profiler.ProfileData`.  It keeps:

* per device, every event on the device plane's op lines (each HLO op the
  programs ran: Pallas kernels, XLA fusions, copies, collectives, and the
  asynchronous ones), as the instruction's name, its opcode, and its start
  and end in ns;
* the harness's own host spans (``bench.call``, ``bench.sync``,
  ``bench.check``).

The traced window runs from the first ``bench.call`` to the end of the last
``bench.sync``.  Busy time is the union of a device's op intervals inside
it; an idle gap is a stretch of the window in which no op runs, named by the
ops that bound it.  The device's times are taken as the trace gives them:
they sit about a millisecond off the host's, an error at the window's two
ends only, a ten-thousandth of a 10-second window.
"""

from __future__ import annotations

import bisect
import gzip
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINES = ("XLA Ops", "Async XLA Ops")
SPANS = ("bench.call", "bench.sync", "bench.check")
# "%name = <shape> opcode(operands), ...": the first "opcode(" after " = ".
OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


@dataclass(frozen=True)
class Interval:
    name: str
    start: float          # ns
    end: float            # ns
    kind: str = ""        # an op's opcode


def op_interval(text: str, start: float, end: float) -> Interval:
    """An op event, named by its HLO instruction's name and opcode."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return Interval(text, start, end)
    m = OPCODE.search(" " + rest)
    return Interval(name.lstrip("%"), start, end, m.group(1) if m else "")


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # "TPU:0" -> [Interval]
    spans: list = field(default_factory=list)     # harness spans

    def window(self) -> tuple:
        """(start, end) in ns of the traced window."""
        calls = [s.start for s in self.spans if s.name == "bench.call"]
        syncs = [s.end for s in self.spans if s.name == "bench.sync"]
        if not calls or not syncs:
            raise ValueError("the trace holds no bench.call/bench.sync spans")
        return min(calls), max(syncs)

    def calls(self) -> list:
        """Every ``bench.call`` span: one per call of the window."""
        return [s for s in self.spans if s.name == "bench.call"]

    def ops(self, device: str, lo: float, hi: float, keep=None) -> list:
        """The device's op intervals clipped to [lo, hi] (optionally only
        those that ``keep`` accepts)."""
        out = []
        for op in self.devices[device]:
            s, e = max(op.start, lo), min(op.end, hi)
            if e > s and (keep is None or keep(op)):
                out.append(Interval(op.name, s, e, op.kind))
        return out

    def busy_ns(self, device: str, lo: float, hi: float) -> float:
        return total(merge(self.ops(device, lo, hi)))

    def exposed_ns(self, device: str, lo: float, hi: float, pred) -> tuple:
        """(time in which an op that ``pred`` accepts runs, the part of it
        in which no other op runs) on ``device`` inside [lo, hi], in ns."""
        mine = merge(self.ops(device, lo, hi, keep=pred))
        rest = merge(self.ops(device, lo, hi, keep=lambda op: not pred(op)))
        return total(mine), total(subtract(mine, rest))

    def top_ops(self, n: int) -> list:
        """The ``n`` op names that took most device time in the window,
        ``[name, seconds]``, averaged over devices."""
        lo, hi = self.window()
        acc = defaultdict(float)
        for dev in self.devices:
            for op in self.ops(dev, lo, hi):
                label = f"{op.name} ({op.kind})" if op.kind else op.name
                acc[label] += (op.end - op.start) / 1e9
        nd = max(len(self.devices), 1)
        ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[name, secs / nd] for name, secs in ranked]

    def idle_gaps(self, n: int) -> list:
        """The ``n`` longest idle gaps of any device in the window,
        ``["<op before> .. <op after> on <device>", seconds]``; the window's
        edges stand for a missing op."""
        lo, hi = self.window()
        gaps = []
        for dev in self.devices:
            by_end = sorted(self.ops(dev, lo, hi), key=lambda op: op.end)
            by_start = sorted(by_end, key=lambda op: op.start)
            ends = [op.end for op in by_end]
            starts = [op.start for op in by_start]
            for s, e in complement(merge(by_end), lo, hi):
                i = bisect.bisect_right(ends, s) - 1
                j = bisect.bisect_left(starts, e)
                before = by_end[i].name if s > lo else "window start"
                after = by_start[j].name if e < hi else "window end"
                gaps.append((e - s, f"{before} .. {after} on {dev}"))
        gaps.sort(key=lambda g: -g[0])
        return [[label, length / 1e9] for length, label in gaps[:n]]


def merge(intervals) -> list:
    """The union of ``intervals`` as sorted, disjoint ``(start, end)``."""
    out = []
    for iv in sorted(intervals, key=lambda iv: iv.start):
        if out and iv.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], iv.end)
        else:
            out.append([iv.start, iv.end])
    return [tuple(p) for p in out]


def total(pairs) -> float:
    return sum(e - s for s, e in pairs)


def complement(pairs, lo: float, hi: float) -> list:
    """The stretches of [lo, hi] that sorted disjoint ``pairs`` leave out."""
    out, t = [], lo
    for s, e in pairs:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def subtract(pairs, cut) -> list:
    """``pairs`` minus ``cut``, both sorted and disjoint."""
    out, j = [], 0
    for s, e in pairs:
        while j < len(cut) and cut[j][1] <= s:
            j += 1
        t, k = s, j
        while k < len(cut) and cut[k][0] < e:
            cs, ce = cut[k]
            if cs > t:
                out.append((t, cs))
            t = max(t, ce)
            k += 1
        if t < e:
            out.append((t, e))
    return out


def from_profile(profile) -> Trace:
    """A :class:`Trace` from a :class:`jax.profiler.ProfileData`."""
    tr = Trace()
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops = []
            for line in plane.lines:
                if line.name in OPS_LINES:
                    ops += [op_interval(e.name, e.start_ns,
                                        e.start_ns + e.duration_ns)
                            for e in line.events]
            tr.devices[f"TPU:{m.group(1)}"] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans += [Interval(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
                             for e in line.events if e.name in SPANS]
    tr.spans.sort(key=lambda s: s.start)
    return tr


def load(path) -> Trace:
    """Read a ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData
    data = Path(path).read_bytes()
    if str(path).endswith(".gz"):
        data = gzip.decompress(data)
    return from_profile(ProfileData.from_serialized_xspace(data))
