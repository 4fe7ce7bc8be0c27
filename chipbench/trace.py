"""Reduce a JAX profiler trace (`.xplane.pb`) to the benchmark's numbers.

The trace holds planes (a device, a host), each with lines of events that
have a name, a start and a duration in nanoseconds on one clock. On a TPU
the device plane is ``/device:TPU:<n>`` with one line of the operations
XLA ran (``XLA Ops``) and one of the programs (``XLA Modules``). The
benchmark's own host spans are `jax.profiler.TraceAnnotation` events on
the host plane, named ``chipbench.<span>``.

From these:

- busy: the union of the device's operation intervals inside the
  measured window (``chipbench.window``), averaged over the devices;
- time of an operation or program, matched by a regular expression
  against the start of its name: on a TPU an operation's event is
  named by its HLO text (``%_fleet_scan_padded.1 = f32[...]
  custom-call(...)``), of which the part before `` = `` is its own
  name; a Pallas kernel is named after the jitted function that calls
  `pallas_call` (``_fleet_scan_padded``, ``jit__pallas_fwd``), a
  program by its jitted function (``jit__backtest_jit(<hash>)``);
- the device operations that took most time, and the idle gaps of the
  device attributed to the host span that was open over them.
"""

from __future__ import annotations

import glob
import re
from collections import defaultdict
from typing import NamedTuple, Optional

SPAN_PREFIX = "chipbench."
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    stats: dict

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    @property
    def op_name(self) -> str:
        """The operation's own name: its HLO text up to `` = ``, without
        the ``%`` and the instance number."""
        return re.sub(r"\.\d+$", "", self.name.split(" = ")[0].lstrip("%"))

    def matches(self, pattern: re.Pattern) -> bool:
        return bool(pattern.match(self.op_name))


def read_events(path: str) -> list[Event]:
    """Every event of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 dict(e.stats)))
    return out


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Trace:
    """The device operations, programs and host spans of one window.

    ``device_prefix`` selects the device planes (``/device:TPU`` on the
    chip). Only events that overlap the ``chipbench.window`` span count,
    clipped to it.
    """

    def __init__(self, events: list[Event], *,
                 device_prefix: str = "/device:TPU"):
        spans = [e for e in events if e.name.startswith(SPAN_PREFIX)]
        windows = [e for e in spans if e.name == WINDOW_SPAN]
        if not windows:
            raise ValueError(f"trace has no {WINDOW_SPAN} span")
        self.t0 = min(e.start_ns for e in windows)
        self.t1 = max(e.end_ns for e in windows)
        inside = lambda e: e.end_ns > self.t0 and e.start_ns < self.t1  # noqa: E731
        dev = [e for e in events
               if e.plane.startswith(device_prefix) and inside(e)]
        self.devices = sorted({e.plane for e in dev})
        self.ops = [e for e in dev if e.line == OPS_LINE]
        self.modules = [e for e in dev if e.line == MODULES_LINE]
        self.spans = [e for e in spans if e.name != WINDOW_SPAN and inside(e)]

    @classmethod
    def from_dir(cls, trace_dir: str, **kw) -> "Trace":
        return cls(read_events(find_xplane(trace_dir)), **kw)

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _clip(self, e: Event) -> tuple[float, float]:
        return max(e.start_ns, self.t0), min(e.end_ns, self.t1)

    def busy_intervals(self, device: str) -> list[tuple[float, float]]:
        return _union([self._clip(e) for e in self.ops
                       if e.plane == device])

    def busy_s(self) -> Optional[float]:
        """Seconds in which an operation ran, averaged over the devices;
        None when the trace holds no device operation."""
        if not self.devices:
            return None
        total = sum(e - s for d in self.devices
                    for s, e in self.busy_intervals(d))
        return total * 1e-9 / len(self.devices)

    def op_s(self, pattern: str) -> float:
        """Device seconds of the operations whose own name matches
        ``pattern`` from its start, summed over the devices."""
        rx = re.compile(pattern)
        return sum(b - a for a, b in (self._clip(e) for e in self.ops
                                      if e.matches(rx))) * 1e-9

    def module_s(self, pattern: str) -> float:
        """Device seconds of the programs whose name matches ``pattern``
        from its start."""
        rx = re.compile(pattern)
        return sum(b - a for a, b in (self._clip(e) for e in self.modules
                                      if e.matches(rx))) * 1e-9

    def top_ops(self, n: int = 10) -> list[list]:
        """The ``n`` device operations that took most time, by name."""
        total: dict[str, float] = defaultdict(float)
        for e in self.ops:
            a, b = self._clip(e)
            total[e.op_name] += (b - a) * 1e-9
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """Idle seconds of the first device, summed by the innermost host
        span open at each idle moment (``host`` where none is); the ``n``
        largest."""
        if not self.devices:
            return []
        busy = self.busy_intervals(self.devices[0])
        gaps, cur = [], self.t0
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.t1:
            gaps.append((cur, self.t1))
        # the innermost open span (the latest started) owns each moment:
        # sweep the span edges into owned segments, then intersect them
        # with the gaps in one merge
        edges = sorted([(e.start_ns, 1, i) for i, e in enumerate(self.spans)]
                       + [(e.end_ns, 0, i) for i, e in enumerate(self.spans)])
        segments, open_, last = [], {}, self.t0
        for t, is_start, i in edges:
            if t > last:
                owner = max(open_.items(), key=lambda kv: kv[1])[0] \
                    if open_ else None
                segments.append((last, t, owner))
                last = t
            if is_start:
                open_[i] = t
            else:
                open_.pop(i, None)
        segments.append((last, float("inf"), None))
        total: dict[str, float] = defaultdict(float)
        j = 0
        for gs, ge in gaps:
            while segments[j][1] <= gs:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < ge:
                a, b, owner = segments[k]
                name = self.spans[owner].name[len(SPAN_PREFIX):] \
                    if owner is not None else "host"
                total[name] += (min(b, ge) - max(a, gs)) * 1e-9
                k += 1
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]
