#!/usr/bin/env python3
"""The program's own spans and named scopes in a device trace.

`chipbench/trace.py` reduces the benchmark's window to device busy time,
XLA's own operation and program names, and the benchmark's host spans
(``chipbench.*``). The program puts more into the same trace, on the
same clock:

- program spans: host events named ``repro.<span>``
  (`repro.obs.profiling.profiled`), whose keyword attributes are the
  event's stats (``bytes``, ``rows``, ...);
- op names: each device operation's ``op_name``, the JAX name stack it
  was traced under (``jit(_backtest_jit)/backtest.gather/gather``),
  with the `jax.named_scope` names in it. The trace does not give it on
  the operation's event: the ``/host:metadata`` plane holds each
  program's optimized HLO (an ``xla.HloProto``, under the stat ``Hlo
  Proto`` of the event metadata named like the program's ``XLA
  Modules`` event), where every instruction carries it. An operation
  belongs to the program whose ``XLA Modules`` event contains it.

`ProgramTrace` adds both to `Trace`: host seconds by program span
(`span_s`), device seconds by named scope over leaf operations
(`scope_s`; ``while``, ``conditional`` and ``call`` events contain
their body's operations on the same line, so they never count), and
idle time by the innermost span of both families (`idle_by_span`:
``grid/grid.thresholds``), and each span's cost per unit of its
counters (`ns_per_unit`: per resolution, per byte uploaded).

The run deletes its window's trace before the per-layer readers run,
and its `Trace` keeps neither the program's host events nor the
metadata plane. So the readers of these metrics trace a short window of
their own (`recorded`): the calls that follow the measured window,
through the same entry, for at least ``RECORD_SECONDS``. By hand:

    python3 chipbench/program_trace.py --workload <cell> --seed <n> \
        --calls <k> [--keep <file>]

sets a cell up as a run does, traces ``k`` calls and prints their
breakdown as one JSON object; ``--keep`` copies the ``.xplane.pb``.
"""

from __future__ import annotations

import argparse
import bisect
import importlib
import json
import re
import shutil
import sys
import tempfile
import time
from collections import defaultdict
from pathlib import Path
from typing import Iterator, Optional

if __name__ == "__main__":
    HERE = Path(__file__).resolve().parent
    # the script's own directory would shadow the standard library's `trace`
    sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
    sys.path.insert(0, str(HERE.parent))

import jax  # noqa: E402

from chipbench import data  # noqa: E402
from chipbench.trace import SPAN_PREFIX, Event, Trace, find_xplane, \
    read_events  # noqa: E402

PROGRAM_PREFIX = "repro."
CALL_SPAN = SPAN_PREFIX + "call"
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"
# operations whose event spans the operations of the computations they run
CONTAINERS = frozenset({"while", "conditional", "call"})
RECORD_SECONDS = 1.0


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf) -> Iterator[tuple[int, object]]:
    """(field number, value) of one serialized message: an int for a
    varint, a memoryview for a length-delimited field (fixed-width
    fields are skipped)."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield key >> 3, v
        elif wire == 2:
            size, i = _varint(buf, i)
            yield key >> 3, buf[i:i + size]
            i += size
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")


def _first(buf, field: int):
    return next((v for f, v in _fields(buf) if f == field), None)


def _str(v) -> str:
    return bytes(v).decode() if v is not None else ""


def hlo_instructions(hlo_proto) -> dict[str, tuple[str, str]]:
    """Instruction name -> (opcode, op_name) over every computation of
    one serialized ``xla.HloProto``."""
    out = {}
    module = _first(hlo_proto, 1)                    # HloProto.hlo_module
    for f, comp in _fields(module):
        if f != 3:                                   # .computations
            continue
        for g, ins in _fields(comp):
            if g != 2:                               # .instructions
                continue
            name = opcode = op_name = ""
            for h, v in _fields(ins):
                if h == 1:
                    name = _str(v)
                elif h == 2:
                    opcode = _str(v)
                elif h == 7:                         # .metadata.op_name
                    op_name = _str(_first(v, 2))
            out[name] = (opcode, op_name)
    return out


def read_hlo(path: str) -> dict[str, dict[str, tuple[str, str]]]:
    """Program name (as its ``XLA Modules`` events are named) -> its
    instructions (`hlo_instructions`), from the metadata plane of one
    ``.xplane.pb``."""
    space = memoryview(Path(path).read_bytes())
    out = {}
    for f, plane in _fields(space):
        if f != 1 or _str(_first(plane, 2)) != METADATA_PLANE:
            continue
        stat_ids = set()
        for g, entry in _fields(plane):             # stat_metadata map
            if g == 5:
                meta = _first(entry, 2)
                if _str(_first(meta, 2)) == HLO_STAT:
                    stat_ids.add(_first(meta, 1))
        for g, entry in _fields(plane):             # event_metadata map
            if g != 4:
                continue
            meta = _first(entry, 2)
            name, proto = "", None
            for h, v in _fields(meta):
                if h == 2:
                    name = _str(v)
                elif h == 5 and _first(v, 1) in stat_ids:
                    proto = _first(v, 6)             # XStat.bytes_value
            if proto is not None:
                out[name] = hlo_instructions(proto)
    return out


# -- the reduction ------------------------------------------------------------

def scope_segments(op_name: str) -> list[str]:
    """The name stack's segments with transformation wrappers removed:
    ``transpose(jvp(tune.objective))/mul`` -> ``tune.objective``,
    ``mul``."""
    return re.sub(r"\w+\(|\)", "", op_name).split("/")


def _innermost(spans: list[Event], t0: float
               ) -> list[tuple[float, float, Optional[Event]]]:
    """Consecutive segments from ``t0`` on, each owned by the innermost
    (the latest started) of ``spans`` open over it, None where none is;
    the last segment runs to infinity."""
    edges = sorted([(e.start_ns, 1, i) for i, e in enumerate(spans)]
                   + [(e.end_ns, 0, i) for i, e in enumerate(spans)])
    segments, open_, last = [], {}, t0
    for t, is_start, i in edges:
        if t > last:
            owner = max(open_.items(), key=lambda kv: kv[1])[0] \
                if open_ else None
            segments.append((last, t, spans[owner] if owner is not None
                             else None))
            last = t
        if is_start:
            open_[i] = t
        else:
            open_.pop(i, None)
    segments.append((last, float("inf"), None))
    return segments


def _split(pieces: list[tuple], segments: list[tuple]) -> list[tuple]:
    """Intersect labelled pieces with owned segments (both sorted and
    disjoint, the segments covering every piece): each part gains the
    segment's owner as one more label."""
    out, j = [], 0
    for a, b, labels in pieces:
        while segments[j][1] <= a:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < b:
            s, e, owner = segments[k]
            out.append((max(a, s), min(b, e), labels + (owner,)))
            k += 1
    return out


class ProgramTrace(Trace):
    """A `Trace` with the program's spans and each operation's op name.

    ``hlo`` maps a program's name to its instructions (`read_hlo`).
    ``calls`` is the number of the benchmark's ``chipbench.call`` spans
    in the window.
    """

    def __init__(self, events: list[Event],
                 hlo: dict[str, dict[str, tuple[str, str]]], **kw):
        super().__init__(events, **kw)
        self.program_spans = [
            e for e in events if e.name.startswith(PROGRAM_PREFIX)
            and e.end_ns > self.t0 and e.start_ns < self.t1]
        self.calls = sum(e.name == CALL_SPAN for e in self.spans)
        self._leaves = self._leaf_ops(hlo)

    @classmethod
    def from_file(cls, path: str, **kw) -> "ProgramTrace":
        return cls(read_events(path), read_hlo(path), **kw)

    def _leaf_ops(self, hlo) -> list[tuple[Event, str, str]]:
        """(event, instruction name, op name) of every operation that is
        no container, the op name from the HLO of the program whose
        event contains it ("" where no program's HLO names it)."""
        modules = defaultdict(list)
        for m in sorted(self.modules, key=lambda m: m.start_ns):
            modules[m.plane].append(m)
        starts = {d: [m.start_ns for m in ms] for d, ms in modules.items()}
        out = []
        for e in self.ops:
            j = bisect.bisect_right(starts.get(e.plane, []), e.start_ns) - 1
            prog = modules[e.plane][j] if j >= 0 else None
            instrs = hlo.get(prog.name, {}) \
                if prog is not None and e.start_ns < prog.end_ns else {}
            instr = e.name.split(" = ")[0].lstrip("%")
            opcode, op_name = instrs.get(instr, ("", ""))
            if opcode not in CONTAINERS:
                out.append((e, instr, op_name))
        return out

    def op_names(self) -> list[tuple[str, str]]:
        """(instruction name, op name) of every leaf operation."""
        return [(instr, name) for _, instr, name in self._leaves]

    def scope_s(self, scope: str, op: Optional[str] = None) -> float:
        """Device seconds of the leaf operations whose op name has
        ``scope`` as a segment (under any transformation), summed over
        the devices; ``op``, a pattern, keeps only the operations whose
        own name matches it from its start."""
        rx = re.compile(op) if op is not None else None
        total = 0.0
        for e, _, name in self._leaves:
            if scope in scope_segments(name) \
                    and (rx is None or e.matches(rx)):
                a, b = self._clip(e)
                total += b - a
        return total * 1e-9

    def scopes_s(self) -> dict[str, float]:
        """Device seconds of the leaf operations by the innermost named
        scope of their op name (a segment with a dot in it), else by
        the operation's own name in brackets."""
        out: dict[str, float] = defaultdict(float)
        for e, _, name in self._leaves:
            named = [s for s in scope_segments(name) if "." in s]
            a, b = self._clip(e)
            out[named[-1] if named else f"[{e.op_name}]"] += (b - a) * 1e-9
        return dict(out)

    def span_s(self, *names: str) -> float:
        """Host seconds inside the window of the program spans named
        ``repro.<name>`` for each of ``names``."""
        want = {PROGRAM_PREFIX + n for n in names}
        return sum(b - a for a, b in (self._clip(e)
                                      for e in self.program_spans
                                      if e.name in want)) * 1e-9

    def ns_per_unit(self, name: str) -> dict[str, float]:
        """Host nanoseconds of the program spans named ``repro.<name>``
        per unit of each of their counters (numeric stats: ``bytes``,
        ``resolutions``, ...), over the spans wholly inside the window:
        ``{"bytes": 0.34}`` reads one byte every 0.34 ns, 2.9 GB/s."""
        ns, units = 0.0, defaultdict(float)
        for e in self.program_spans:
            if e.name != PROGRAM_PREFIX + name or e.start_ns < self.t0 \
                    or e.end_ns > self.t1:
                continue
            ns += e.dur_ns
            for k, v in e.stats.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    units[k] += v
        return {k: ns / u for k, u in units.items() if u}

    def _idle_pieces(self, *families: list[Event]) -> list[tuple]:
        """The first device's idle intervals, cut where the innermost
        open span of each family changes: (start, end, owners)."""
        if not self.devices:
            return []
        pieces, cur = [], self.t0
        for s, e in self.busy_intervals(self.devices[0]):
            if s > cur:
                pieces.append((cur, s, ()))
            cur = max(cur, e)
        if cur < self.t1:
            pieces.append((cur, self.t1, ()))
        for spans in families:
            pieces = _split(pieces, _innermost(spans, self.t0))
        return pieces

    def idle_named(self) -> list[tuple[float, float, str]]:
        """The first device's idle intervals, cut where the innermost
        open benchmark span or program span changes, each named by both
        (``grid/grid.thresholds``; ``grid`` where no program span is,
        ``host`` where no benchmark span is)."""
        out = []
        for a, b, (bench, prog) in self._idle_pieces(self.spans,
                                                     self.program_spans):
            name = bench.name[len(SPAN_PREFIX):] if bench else "host"
            if prog is not None:
                name += "/" + prog.name[len(PROGRAM_PREFIX):]
            out.append((a, b, name))
        return out

    def idle_by_span(self, n: int = 10) -> list[list]:
        """Idle seconds of the first device by name (`idle_named`); the
        ``n`` largest. `Trace.idle_gaps` names benchmark spans alone."""
        total: dict[str, float] = defaultdict(float)
        for a, b, name in self.idle_named():
            total[name] += (b - a) * 1e-9
        return [[k, v] for k, v in
                sorted(total.items(), key=lambda kv: -kv[1])[:n]]

    def idle_in_s(self, prefix: str, *, but: tuple[str, ...] = ()
                  ) -> float:
        """Idle seconds of the first device in which the innermost open
        program span named ``repro.<prefix>*`` is none of ``but``."""
        family = [e for e in self.program_spans
                  if e.name.startswith(PROGRAM_PREFIX + prefix)]
        skip = {PROGRAM_PREFIX + b for b in but}
        return sum(b - a for a, b, (owner,) in self._idle_pieces(family)
                   if owner is not None and owner.name not in skip) * 1e-9


# -- recording ----------------------------------------------------------------

def record(entry, start: int, *, calls: Optional[int] = None,
           seconds: float = RECORD_SECONDS,
           keep: Optional[str] = None) -> ProgramTrace:
    """Trace window calls ``start``, ``start + 1``, ... of ``entry``
    (``calls`` of them, else until ``seconds`` have passed, at least
    one) as a run traces its window, and read the trace. The entry's
    host span statistics are left as they were. ``keep`` is a path to
    copy the ``.xplane.pb`` to."""
    saved = {k: list(v) for k, v in entry.spans.seconds.items()}
    trace_dir = tempfile.mkdtemp(prefix="chipbench_program_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(SPAN_PREFIX + "window"):
            t0, i = time.perf_counter(), start
            while True:
                with entry.spans("call"):
                    entry.call(data.WINDOW, i)
                i += 1
                if (i - start >= calls) if calls is not None \
                        else time.perf_counter() - t0 >= seconds:
                    break
    finally:
        jax.profiler.stop_trace()
        entry.spans.seconds.clear()
        entry.spans.seconds.update(saved)
    try:
        path = find_xplane(trace_dir)
        if keep is not None:
            shutil.copyfile(path, keep)
        return ProgramTrace.from_file(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)


def recorded(ctx) -> ProgramTrace:
    """The program trace of the calls that follow a run's measured
    window, recorded by the first reader that asks and kept on the
    run's reader context."""
    if getattr(ctx, "program_trace", None) is None:
        ctx.program_trace = record(ctx.entry, ctx.attempted)
    return ctx.program_trace


def per_call_ms(seconds: float, pt: ProgramTrace) -> Optional[float]:
    """Milliseconds per traced call; None where nothing was read."""
    return 1e3 * seconds / pt.calls if seconds and pt.calls else None


def breakdown(pt: ProgramTrace) -> dict:
    """Per-call milliseconds of every program span, named scope and
    program, each span's nanoseconds per unit of its counters, and the
    idle time by span, of one program trace; and the longest single
    idle interval with its name."""
    per = lambda s: 1e3 * s / max(pt.calls, 1)  # noqa: E731
    idle = pt.idle_named()
    spans = sorted({e.name[len(PROGRAM_PREFIX):] for e in pt.program_spans})
    return {
        "calls": pt.calls, "window_s": pt.window_s, "busy_s": pt.busy_s(),
        "span_ms": {s: per(pt.span_s(s)) for s in spans},
        "scope_ms": {k: per(v) for k, v in sorted(
            pt.scopes_s().items(), key=lambda kv: -kv[1])},
        "program_ms": {
            m: per(pt.module_s(re.escape(m) + r"\("))
            for m in sorted({e.name.split("(")[0] for e in pt.modules})},
        "ns_per_unit": {s: r for s in spans if (r := pt.ns_per_unit(s))},
        "idle_ms": [[k, per(v)] for k, v in pt.idle_by_span(20)],
        "longest_idle_ms": max(([(b - a) * 1e-6, name]
                                for a, b, name in idle), default=None),
    }


def main(argv=None) -> int:
    # here, not at the top: while a run reads its metrics, `chipbench.run`
    # is the script running as __main__, and would be loaded twice
    from chipbench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--calls", type=int, required=True)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    try:
        run.find_chips(1)
    except run.NoChip as e:
        print(f"program_trace: {e}", file=sys.stderr)
        return 3
    run.use_compile_cache()
    bench = run.load_json(run.ROOT / "BENCHMARK.json")
    _, config, cell = run.load_cell(bench, args.workload)
    module = importlib.import_module(f"chipbench.entries.{cell['entry']}")
    entry = module.Entry(config, cell, args.seed, run.Spans())
    for k in range(int(cell.get("warmup_calls", 2))):
        entry.call(data.WARMUP, k)
    pt = record(entry, 0, calls=args.calls, keep=args.keep)
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      **breakdown(pt)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
