"""Reduce a profiler trace (``.xplane.pb``) to what the metrics read.

The JAX profiler writes one plane per TPU (``/device:TPU:<n>``) and
one for the host (``/host:CPU``).  On a device plane the line ``XLA
Ops`` holds one event per operation run, named by its HLO text
(``%jacobi_step.6 = f32[...] custom-call(...)``: a fusion, a custom
call such as a Pallas kernel, a collective; a ``while`` loop's event
spans the events of its body), ``Async XLA Ops`` the asynchronous
ones (copies, collectives) from start to done, and ``XLA Modules``
one event per program run, named after the jitted function
(``jit_decode_step(...)``).  The host plane has a line per thread; the
benchmark's spans (``TraceAnnotation``) are on the main thread's line,
among the Python profiler's frames (``$engine.py:360 step``), on the
same clock as the devices.

From these, :func:`reduce` takes:

* the traced window: the host span named ``window``;
* busy time per device: the union of its operations' intervals inside
  the window, and the idle gaps between them;
* operation and program intervals per device, for the readers that
  sum a kernel's or a program's device time;
* collective time with no computation beside it on that device;
* each idle gap named by the innermost host span open over it.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import gzip
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

Interval = Tuple[float, float]          # seconds on the trace's clock

OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"
# an op's name is kept as "<instruction> <opcode>"; collectives are
# told by the opcode
COLLECTIVE = re.compile(r" (collective-permute|all-gather|all-reduce|"
                        r"reduce-scatter|all-to-all)[\w-]*$")
HLO = re.compile(r"^(%[\w.-]+) = .*?\s([a-z][\w-]*)\(")


def short_name(hlo: str) -> str:
    """``%jacobi_step.6 = f32[...] custom-call(...)`` ->
    ``%jacobi_step.6 custom-call``."""
    m = HLO.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:120]


def union(intervals: List[Interval]) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def subtract(xs: List[Interval], ys: List[Interval]) -> List[Interval]:
    """The parts of the disjoint sorted ``xs`` that no ``ys`` covers."""
    ys = union(ys)
    out, j = [], 0
    for a, b in xs:
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k, cur = j, a
        while k < len(ys) and ys[k][0] < b:
            if ys[k][0] > cur:
                out.append((cur, ys[k][0]))
            cur = max(cur, ys[k][1])
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


@dataclasses.dataclass
class Reduced:
    window: Interval
    # per device: [(op name, start, end)] inside the window
    ops: Dict[str, List[Tuple[str, float, float]]]
    # per device: asynchronous ops, start to done
    async_ops: Dict[str, List[Tuple[str, float, float]]]
    # per device: [(program name, start, end)] inside the window
    modules: Dict[str, List[Tuple[str, float, float]]]
    # the main thread's host events: [(name, start, end)]
    spans: List[Tuple[str, float, float]]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def devices(self) -> List[str]:
        return sorted(self.ops)

    def busy(self, dev: str, keep=lambda name: True) -> List[Interval]:
        """When an operation ran on ``dev`` (a loop's own event, which
        also spans the gaps inside its body, left out)."""
        return union([(a, b) for n, a, b in self.ops[dev]
                      if keep(n) and not self._is_loop(n)])

    @property
    def busy_s(self) -> float:
        """Busy seconds, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(total(self.busy(d)) for d in self.ops) / len(self.ops)

    def gaps(self, dev: str) -> List[Interval]:
        return subtract([self.window], self.busy(dev))

    def module_runs(self, pattern: str, dev: Optional[str] = None):
        """[(start, end)] of the program runs whose name matches, on
        ``dev`` (default: the first device)."""
        if not self.modules:
            return []
        rx = re.compile(pattern)
        dev = dev or self.devices[0]
        return [(a, b) for n, a, b in self.modules.get(dev, [])
                if rx.search(n)]

    def exposed_collective_s(self) -> float:
        """Seconds of collective operations during which no other
        operation runs on that device, averaged over the devices."""
        if not self.ops:
            return 0.0
        out = 0.0
        for dev, ops in self.ops.items():
            every = ops + self.async_ops.get(dev, [])
            coll = union([(a, b) for n, a, b in every
                          if COLLECTIVE.search(n)])
            rest = [(a, b) for n, a, b in ops if not COLLECTIVE.search(n)
                    and not self._is_loop(n)]
            out += total(subtract(coll, rest))
        return out / len(self.ops)

    @staticmethod
    def _is_loop(name: str) -> bool:
        """A loop or call whose event spans the ops of its body."""
        return name.endswith((" while", " conditional", " call"))

    def has_collectives(self) -> bool:
        return any(COLLECTIVE.search(n) for d in (self.ops, self.async_ops)
                   for ops in d.values() for n, _, _ in ops)

    def span_at(self, t: float, names=()) -> str:
        """What the host's main thread was doing at time t: the
        innermost of the benchmark's spans (``names``) open then, and
        the innermost event of any kind."""
        inner, mine = None, None
        for name, a, b in self.spans:
            if a <= t <= b and name != WINDOW:
                if inner is None or b - a < inner[2] - inner[1]:
                    inner = (name, a, b)
                if name in names and (mine is None
                                      or b - a < mine[2] - mine[1]):
                    mine = (name, a, b)
        parts = [x[0] for x in (mine, inner) if x is not None]
        return " / ".join(dict.fromkeys(parts)) or "(no span)"

    def breakdown(self, names=(), top: int = 10) -> dict:
        """The device operations that took most time (seconds per
        device; a loop's own event, which spans its body, left out) and
        the longest idle gaps on the first device, each named by what
        the host was doing (:meth:`span_at`)."""
        per: Dict[str, float] = collections.Counter()
        n = max(1, len(self.ops))
        for ops in self.ops.values():
            for name, a, b in ops:
                if not self._is_loop(name):
                    per[name] += (b - a) / n
        ops = sorted(per.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        if self.ops:
            gaps = sorted(self.gaps(self.devices[0]),
                          key=lambda g: g[0] - g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[self.span_at((a + b) / 2, names), b - a]
                              for a, b in gaps]}


def _events(line, name=lambda n: n):
    for e in line.events:
        yield (name(e.name), e.start_ns * 1e-9,
               (e.start_ns + e.duration_ns) * 1e-9)


def reduce(path: str) -> Reduced:
    """Read one ``.xplane.pb`` (or ``.xplane.pb.gz``) and keep what lies
    in the window."""
    from jax.profiler import ProfileData

    with (gzip.open if path.endswith(".gz") else open)(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    host, ops, async_ops, modules = [], {}, {}, {}
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            host.extend(list(_events(line)) for line in plane.lines)
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = list(_events(line, short_name))
                elif line.name == ASYNC_LINE:
                    async_ops[plane.name] = list(_events(line, short_name))
                elif line.name == MODULES_LINE:
                    modules[plane.name] = list(_events(line))
    main = [evs for evs in host if any(n == WINDOW for n, _, _ in evs)]
    if not main:
        raise ValueError(f"{path}: no host span named {WINDOW!r}")
    lo, hi = next((a, b) for n, a, b in main[0] if n == WINDOW)

    def inside(evs):
        return [(n, max(a, lo), min(b, hi)) for n, a, b in evs
                if b > lo and a < hi]

    return Reduced(window=(lo, hi),
                   ops={d: inside(e) for d, e in ops.items()},
                   async_ops={d: inside(e) for d, e in async_ops.items()},
                   modules={d: inside(e) for d, e in modules.items()},
                   spans=inside(main[0]))


@contextlib.contextmanager
def recording(enabled: bool):
    """Profile the body when ``enabled``; yields a list that holds the
    path of the written ``.xplane.pb`` once the body is done.  The
    files go to a temporary directory removed by :func:`discard`."""
    found: List[str] = []
    if not enabled:
        yield found
        return
    import jax

    where = tempfile.mkdtemp(prefix="chipbench-trace-")
    jax.profiler.start_trace(where)
    try:
        yield found
    finally:
        jax.profiler.stop_trace()
        found.extend(glob.glob(os.path.join(where, "**", "*.xplane.pb"),
                               recursive=True))
        found.append(where)


def discard(found: List[str]) -> None:
    if found:
        shutil.rmtree(found[-1], ignore_errors=True)
