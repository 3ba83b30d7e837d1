"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are ``/device:TPU:<id>``; on each, the ``XLA Ops`` line holds
one event per operation, named by its HLO text (kept here up to its `` = ``,
as ``%fusion.12``), and the ``XLA Modules`` line one per program run (a
plane without an ops line counts its program runs as its operations).  The
``Async XLA Ops`` line, whose events span an asynchronous copy from its
start to its end, is not counted as work.
The host plane holds the benchmark's own spans (one ``StepTraceAnnotation``
per unit of work, named by the driver module) and JAX's host events beside them.
All times are on the host's clock, in nanoseconds, as the profiler stores
them.
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)\b")
COLLECTIVE = re.compile(
    r"(all-gather|reduce-scatter|all-reduce|all-to-all|collective-permute|"
    r"collective-broadcast|send|recv)", re.IGNORECASE)


# --------------------------------------------------------------------------- #
# interval arithmetic
# --------------------------------------------------------------------------- #
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint intervals covering exactly the given ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals: Iterable[Interval]) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out: List[Interval] = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """Idle intervals of ``[lo, hi]`` between the disjoint sorted ``busy``."""
    return subtract([(lo, hi)], busy)


# --------------------------------------------------------------------------- #
# the trace
# --------------------------------------------------------------------------- #
class Trace:
    """Events of one traced window: per device, its operations and program
    runs; on the host, the thread that ran the benchmark's spans."""

    def __init__(self, ops: Dict[int, List[Tuple[str, float, float]]],
                 modules: Dict[int, List[Tuple[str, float, float]]],
                 host: List[Tuple[str, float, float]], span: str):
        self.ops, self.modules, self.host, self.span = ops, modules, host, span
        steps = [(s, e) for n, s, e in host if n == span]
        if not steps:
            raise ValueError(f"no {span!r} span in the trace")
        self.steps = steps
        self.lo = min(s for s, _ in steps)
        self.hi = max(e for _, e in steps)
        self.window_s = (self.hi - self.lo) / 1e9
        if not any(ops.values()):
            raise ValueError("no device operation in the trace")
        for dev, evs in ops.items():
            if max((e for _, _, e in evs), default=0) < steps[-1][0]:
                raise ValueError(
                    f"device {dev}'s events end before the last {span!r} "
                    "span starts: the trace dropped events; trace fewer "
                    "units of work")

    # ---------------------------------------------------------------- loading
    @classmethod
    def load(cls, logdir, device_ids: Sequence[int], span: str) -> "Trace":
        from jax.profiler import ProfileData
        paths = sorted(Path(logdir).rglob("*.xplane.pb"))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {logdir}")
        return cls.from_profile(ProfileData.from_file(str(paths[-1])),
                                device_ids, span)

    @classmethod
    def from_profile(cls, pd, device_ids: Sequence[int], span: str
                     ) -> "Trace":
        ops: Dict[int, list] = {}
        modules: Dict[int, list] = {}
        host: list = []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m and int(m.group(1)) in device_ids:
                dev = int(m.group(1))
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        ops.setdefault(dev, []).extend(
                            (n.split(" = ")[0], s, e)
                            for n, s, e in _events(line))
                    elif line.name == "XLA Modules":
                        modules[dev] = _events(line)
                if not ops.get(dev):
                    ops[dev] = list(modules.get(dev, []))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    evs = _events(line)
                    if any(n == span for n, _, _ in evs):
                        host.extend(evs)
        return cls(ops, modules, host, span)

    # ---------------------------------------------------------------- numbers
    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def busy(self, dev: int) -> List[Interval]:
        return clip(union((s, e) for _, s, e in self.ops[dev]),
                    self.lo, self.hi)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(length(self.busy(d)) for d in self.devices) / (
            1e9 * len(self.devices))

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    def program_s(self, pattern: str) -> Optional[float]:
        """Seconds of the program runs whose name matches ``pattern``,
        averaged over the devices; None where no run matches."""
        rx = re.compile(pattern)
        per = []
        for d in self.devices:
            runs = [(s, e) for n, s, e in self.modules.get(d, [])
                    if rx.search(n)]
            per.append(length(clip(union(runs), self.lo, self.hi)))
        if not any(per):
            return None
        return sum(per) / (1e9 * len(per))

    def collective_exposed_s(self) -> Optional[float]:
        """Seconds a device runs a collective while no other operation runs
        on it, averaged over the devices; None where no collective ran."""
        per, seen = [], False
        for d in self.devices:
            coll = union((s, e) for n, s, e in self.ops[d]
                         if COLLECTIVE.search(n))
            seen = seen or bool(coll)
            comp = union((s, e) for n, s, e in self.ops[d]
                         if not COLLECTIVE.search(n))
            per.append(length(clip(subtract(coll, comp), self.lo, self.hi)))
        if not seen:
            return None
        return sum(per) / (1e9 * len(per))

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operations that took most device time, in seconds per
        device."""
        tot: Dict[str, float] = {}
        for d in self.devices:
            for name, s, e in self.ops[d]:
                tot[name] = tot.get(name, 0.0) + (e - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, t / (1e9 * len(self.devices))] for name, t in top]

    def host_at(self, t: float) -> str:
        """The innermost host event open at ``t`` on the benchmark's thread,
        under the benchmark span that holds it."""
        open_ = [(e - s, n) for n, s, e in self.host if s <= t < e]
        if not open_:
            return "outside any span"
        inner = min(open_)[1]
        return inner if inner == self.span else f"{self.span}/{inner}"

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The ``n`` longest idle gaps of the first device, each named by
        what the host was doing at its middle."""
        d = self.devices[0]
        g = sorted(gaps(self.busy(d), self.lo, self.hi),
                   key=lambda iv: iv[0] - iv[1])[:n]
        return [[self.host_at((s + e) / 2), (e - s) / 1e9] for s, e in g]

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}


def _events(line) -> List[Tuple[str, float, float]]:
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]
