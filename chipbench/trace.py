"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time (the union of the intervals in which an operation
ran on a device), the time of named operations, the host spans the
benchmark's own ``TraceAnnotation``s wrote, and the idle gaps named by
what the host was doing in them.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes;
:class:`Trace` holds plain ``(name, start_ns, end_ns)`` tuples, so the
arithmetic is tested on small hand-made traces.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

__all__ = ["Trace", "load", "union", "clip", "short_name", "is_loop"]

Interval = Tuple[float, float]

# the line of a device plane that holds the operations it ran
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"


def clip(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def union(iv: Sequence[Interval]) -> List[Interval]:
    """Sorted disjoint intervals covering the same points as ``iv``."""
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def short_name(name: str) -> str:
    """A device operation's name without its HLO text: ``%fusion.12 =
    f32[...] fusion(...)`` gives ``%fusion.12``."""
    return name.split(" = ", 1)[0]


def is_loop(name: str) -> bool:
    """A ``while`` operation, whose interval covers the operations of its
    body."""
    head, _, rest = name.partition(" = ")
    return head.startswith("%while") or " while(" in rest


def _total(iv: Sequence[Interval]) -> float:
    return sum(b - a for a, b in iv)


@dataclass
class Trace:
    """``devices``: per device, its operations ``(name, start, end)`` in
    ns; ``host``: the host's named spans on the same clock."""

    devices: Dict[str, List[Tuple[str, float, float]]] = \
        field(default_factory=dict)
    host: List[Tuple[str, float, float]] = field(default_factory=list)

    def span(self, name: str) -> Interval:
        """The extent of the host spans called ``name``."""
        hits = [(a, b) for n, a, b in self.host if n == name]
        if not hits:
            raise KeyError(f"no host span {name!r} in the trace")
        return min(a for a, _ in hits), max(b for _, b in hits)

    def _busy(self, win: Interval) -> Dict[str, List[Interval]]:
        return {d: union(clip([(a, b) for _, a, b in ops], *win))
                for d, ops in self.devices.items() if ops}

    def busy_seconds(self, win: Interval) -> float:
        """Busy time in ``win``, averaged over the devices that ran
        anything."""
        busy = self._busy(win)
        if not busy:
            return 0.0
        return sum(_total(iv) for iv in busy.values()) / len(busy) * 1e-9

    def op_seconds(self, win: Interval, match: str) -> Optional[float]:
        """Summed device time of the operations whose name matches the
        regular expression ``match``, or None where none ran."""
        pat = re.compile(match)
        hits = [(a, b) for ops in self.devices.values()
                for n, a, b in ops if pat.search(n)]
        if not hits:
            return None
        return _total(clip(hits, *win)) * 1e-9

    def span_cover_seconds(self, win: Interval, prefix: str,
                           exclude: Sequence[str] = ()) -> float:
        """Time in ``win`` the host spent inside spans whose names start
        with ``prefix`` (less ``exclude``)."""
        iv = [(a, b) for n, a, b in self.host
              if n.startswith(prefix) and n not in exclude]
        return _total(union(clip(iv, *win))) * 1e-9

    def idle_gaps(self, win: Interval) -> List[Interval]:
        """Intervals of ``win`` in which the first busy device ran
        nothing."""
        busy = self._busy(win)
        if not busy:
            return [win]
        iv = busy[sorted(busy)[0]]
        gaps, t = [], win[0]
        for a, b in iv:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < win[1]:
            gaps.append((t, win[1]))
        return gaps

    def breakdown(self, win: Interval, exclude: Sequence[str] = (),
                  k: int = 10) -> Dict[str, List]:
        """The ``k`` device operations that took most time (loops, which
        hold other operations, left out), and the ``k`` longest idle gaps,
        each named by the host span that covers most of it ("control
        plane" where none does)."""
        per_op: Dict[str, float] = {}
        for ops in self.devices.values():
            for n, a, b in ops:
                lo, hi = max(a, win[0]), min(b, win[1])
                if hi > lo and not is_loop(n):
                    key = short_name(n)
                    per_op[key] = per_op.get(key, 0.0) + (hi - lo) * 1e-9
        top = sorted(per_op.items(), key=lambda x: -x[1])[:k]
        spans = [(n, a, b) for n, a, b in self.host if n not in exclude]
        gaps = []
        for a, b in sorted(self.idle_gaps(win), key=lambda g: g[0] - g[1]):
            best, cover = "control plane", 0.0
            for n, sa, sb in spans:
                c = min(b, sb) - max(a, sa)
                if c > cover:
                    best, cover = n, c
            gaps.append([best, (b - a) * 1e-9])
            if len(gaps) == k:
                break
        return {"device_ops": [[n, s] for n, s in top], "idle_gaps": gaps}


def load(trace_dir: str, host_prefix: str = "chipbench.") -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    tr = Trace()
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = tr.devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(host_prefix):
                        tr.host.append((e.name, e.start_ns,
                                        e.start_ns + e.duration_ns))
    return tr
