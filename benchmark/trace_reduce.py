"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time and idle share over the traced window, the
device operations that took most time, kernel time, and the longest
device idle gaps, each named by the benchmark span that was open on
the host during it.

Read with ``jax.profiler.ProfileData`` alone. The window is the host
span ``bench.window`` that the harness opens around the measured loop;
host spans and device events share the profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Callable, Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
_DEVICE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
#: the line of a device plane that holds one event per executed op
OPS_LINE = "XLA Ops"
#: what a Pallas (Mosaic) kernel's op carries in its HLO text
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
_SUFFIX = re.compile(r"\.\d+$")


def short_name(hlo: str) -> str:
    """``%closed_call.12 = (...) custom-call(...)`` -> ``%closed_call``,
    with `` [tpu_custom_call]`` where the op is a Pallas kernel: the
    trace names a device op by its whole HLO text."""
    name = _SUFFIX.sub("", hlo.split(" = ", 1)[0].strip())
    return name + (" [tpu_custom_call]" if KERNEL_MARK in hlo else "")


def is_kernel(hlo: str) -> bool:
    return KERNEL_MARK in hlo


Interval = Tuple[float, float]


def find_xplane(logdir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def union(iv: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The union of intervals clipped to [lo, hi], as sorted disjoint
    intervals."""
    out: List[Interval] = []
    for a, b in sorted(iv):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def _gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """The events of one trace that the reduction needs, in seconds on
    the profiler's clock."""

    def __init__(self, host_spans: Dict[str, List[Interval]],
                 device_ops: Dict[int, List[Tuple[str, float, float]]]):
        self.host_spans = host_spans
        self.device_ops = device_ops

    @classmethod
    def from_file(cls, path: str, span_names: Sequence[str] = ()
                  ) -> "Trace":
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        want = set(span_names) | {WINDOW_SPAN}
        host: Dict[str, List[Interval]] = {}
        dev: Dict[int, List[Tuple[str, float, float]]] = {}
        for plane in pd.planes:
            m = _DEVICE.match(plane.name)
            if m:
                lines = list(plane.lines)
                ops = [ln for ln in lines if ln.name == OPS_LINE]
                evs = dev.setdefault(int(m.group(2)), [])
                for ln in ops:
                    for e in ln.events:
                        evs.append((e.name, e.start_ns * 1e-9,
                                    (e.start_ns + e.duration_ns) * 1e-9))
            elif plane.name.startswith("/host:"):
                for ln in plane.lines:
                    for e in ln.events:
                        if e.name in want:
                            host.setdefault(e.name, []).append(
                                (e.start_ns * 1e-9,
                                 (e.start_ns + e.duration_ns) * 1e-9))
        return cls(host, dev)

    # -- the window ----------------------------------------------------

    def window(self) -> Optional[Interval]:
        w = self.host_spans.get(WINDOW_SPAN)
        if not w:
            return None
        return (min(a for a, _ in w), max(b for _, b in w))

    def busy(self, dev: int) -> List[Interval]:
        lo, hi = self.window()
        return union([(a, b) for _, a, b in self.device_ops.get(dev, [])],
                      lo, hi)

    def busy_s(self) -> Optional[float]:
        """Seconds in which an op ran, averaged over the devices."""
        if self.window() is None or not self.device_ops:
            return None
        per = [sum(b - a for a, b in self.busy(d)) for d in self.device_ops]
        return sum(per) / len(per)

    def window_s(self) -> Optional[float]:
        w = self.window()
        return None if w is None else w[1] - w[0]

    def idle_share(self) -> Optional[float]:
        """1 - busy / window, in percent, mean over the devices."""
        b, w = self.busy_s(), self.window_s()
        if b is None or not w:
            return None
        return 100.0 * (1.0 - b / w)

    # -- operations ----------------------------------------------------

    def op_seconds(self, match: Callable[[str], bool] = lambda n: True
                   ) -> float:
        """Device seconds in which an op whose HLO text matches ran,
        inside the window: the union of those ops' intervals (ops nest:
        a kernel's custom call sits inside the loop that calls it),
        summed over devices."""
        w = self.window()
        if w is None:
            return 0.0
        lo, hi = w
        return sum(b - a for evs in self.device_ops.values()
                   for a, b in union([(x, y) for n, x, y in evs
                                       if match(n)], lo, hi))

    def top_ops(self, n: int = 10) -> List[list]:
        """The ops that took most device time inside the window, by self
        time (an op's time less that of the ops nested in it), summed
        over devices and over ops of one short name."""
        w = self.window()
        if w is None:
            return []
        lo, hi = w
        tot: Dict[str, float] = {}
        for evs in self.device_ops.values():
            clipped = [(max(a, lo), min(b, hi), name) for name, a, b in evs
                       if min(b, hi) > max(a, lo)]
            clipped.sort(key=lambda e: (e[0], -e[1]))
            own = [b - a for a, b, _ in clipped]
            stack: List[int] = []
            for i, (a, b, _) in enumerate(clipped):
                while stack and clipped[stack[-1]][1] <= a:
                    stack.pop()
                if stack:
                    own[stack[-1]] -= min(b, clipped[stack[-1]][1]) - a
                stack.append(i)
            for (a, b, name), t in zip(clipped, own):
                k = short_name(name)
                tot[k] = tot.get(k, 0.0) + t
        return [[k, v] for k, v in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10, dev: Optional[int] = None
                  ) -> List[list]:
        """The longest device idle gaps inside the window, each named by
        the host span that covers most of it (``host:other`` where no
        benchmark span was open)."""
        w = self.window()
        if w is None or not self.device_ops:
            return []
        d = min(self.device_ops) if dev is None else dev
        lo, hi = w
        gaps = sorted(_gaps(self.busy(d), lo, hi),
                      key=lambda g: g[0] - g[1])[:n]
        out = []
        for a, b in gaps:
            best, cover = "host:other", 0.0
            for name, iv in self.host_spans.items():
                if name == WINDOW_SPAN:
                    continue
                c = sum(x1 - x0 for x0, x1 in union(iv, a, b))
                if c > cover:
                    best, cover = name, c
            out.append([best, b - a])
        return out
