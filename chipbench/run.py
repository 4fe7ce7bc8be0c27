#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell named in ``BENCHMARK.json`` is found by name:
``chipbench/cells/<cell>.json`` gives its configuration
(``chipbench/configs/<config>.json``), its entry
(``chipbench/entries/<entry>.py``) and its parameters; each metric is
read by ``chipbench/metrics/<metric>.py``, or by the reader of its
family (``device_idle.py`` reads ``device_idle.<cell>``). Adding a cell, a
configuration or a metric adds files and a ``BENCHMARK.json`` entry and
edits nothing here.

A run: build the inputs from ``--seed`` on the device and warm up every
shape the window uses (``setup_s``, from process start); call the entry
back to back until ``--seconds`` have passed (the window ends with its
last call); read the device's peak memory; free the program's state;
then compare a sample of the window's answers, drawn from the seed, with
the plain reference. ``--trace 1`` records the window with the JAX
profiler and reports the per-layer metrics instead of the end-to-end
ones. The last line of standard output is one JSON object; the numbers
compared, each beside its limit, are the last lines of standard error;
before them it gives the set-up's phases, the window's slowest call, the
compiles and garbage collections inside the window, and the statistics
of each host span.

Without a TPU, or with fewer chips than the cell asks for, the run
prints no result and exits non-zero.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402
from collections import defaultdict  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)
# the script's own directory would shadow the standard library's `trace`
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]

import numpy as np  # noqa: E402

from chipbench import data  # noqa: E402

CACHE_DIR = HERE / ".jax_cache"
MAX_KEEP = 200_000          # window calls a sample is drawn from


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


class Spans:
    """Host spans of the benchmark's own calls into the program: host
    seconds per name, and a `TraceAnnotation` so a trace shows them."""

    def __init__(self):
        self.seconds: dict[str, list[float]] = defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(f"chipbench.{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.seconds[name].append(time.perf_counter() - t0)

    def clear(self) -> None:
        self.seconds.clear()


class WindowWatch:
    """What else ran in the measured window: compiles (JAX's monitoring
    events), garbage collections, and the process's CPU seconds."""

    def __init__(self):
        import gc
        import jax
        self.events: dict[str, int] = defaultdict(int)
        self.gc_s, self._gc_t0, self.on = 0.0, None, False
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)

    def _event(self, name, secs, **kw):
        if self.on and "compil" in name:
            self.events[name.rsplit("/", 1)[-1]] += 1

    def _gc(self, phase, info):
        if not self.on:
            return
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_s += time.perf_counter() - self._gc_t0
            self.events["gc_gen%d" % info["generation"]] += 1

    @contextlib.contextmanager
    def __call__(self):
        c0 = time.process_time()
        self.on = True
        try:
            yield
        finally:
            self.on = False
            self.cpu_s = time.process_time() - c0

    def summary(self) -> str:
        return (f"{dict(self.events)}, gc {self.gc_s * 1e3:.1f} ms, "
                f"process cpu {self.cpu_s:.3f} s")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_metric(name: str):
    """The reader of metric ``name``: ``metrics/<name>.py``, else the
    reader of its family, the name without its last ``.<part>``
    (``device_idle.sweep`` is read by ``metrics/device_idle.py``)."""
    path = HERE / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = HERE / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(bench: dict, workload: str, config_over: dict | None = None,
              cell_over: dict | None = None) -> tuple[dict, dict, dict]:
    """The ``BENCHMARK.json`` entry of ``workload``, its configuration
    and its cell file, each found by name (with overrides for tests)."""
    wl = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if wl is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    config = {**load_json(HERE / "configs" / f"{wl['config']}.json"),
              **(config_over or {})}
    cell = {**load_json(HERE / "cells" / f"{workload}.json"),
            **(cell_over or {})}
    return wl, config, cell


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def use_compile_cache() -> str:
    import jax
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", d)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return d


def find_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX found "
                     f"{len(devices)}")
    return devices


def compare(readings: list[dict], limits: dict) -> list[dict]:
    """One line per number compared: its worst reading over the checked
    calls beside its limit (``max``: at most; ``min``: at least)."""
    out = []
    for name, lim in limits.items():
        vals = [r[name] for r in readings]
        if "max" in lim:
            v = max(vals, key=lambda x: (x != x, x))   # NaN is worst
            ok = v <= lim["max"]
            out.append({"name": name, "value": v, "limit": lim["max"],
                        "ok": bool(ok), "side": "max"})
        else:
            v = min(vals, key=lambda x: (x == x, x))
            ok = v >= lim["min"]
            out.append({"name": name, "value": v, "limit": lim["min"],
                        "ok": bool(ok), "side": "min"})
    return out


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, config_over: dict | None = None,
             cell_over: dict | None = None, require_chip: bool = True,
             wrap_entry=None, log=print) -> dict:
    """One run of ``workload``; returns the result line as a dict, with
    the numbers compared under ``checks``.

    The keyword arguments after ``trace`` serve the tests: sizes
    overridden, the look for a chip skipped, and the entry wrapped to
    plant a fault underneath the timed path.
    """
    import jax
    t_import = time.perf_counter()

    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    wl, config, cell = load_cell(bench, workload, config_over, cell_over)
    devices = find_chips(int(wl["chips"])) if require_chip else jax.devices()
    dev = devices[0]
    t_chip = time.perf_counter()
    cache = use_compile_cache() if require_chip else None

    module = importlib.import_module(f"chipbench.entries.{cell['entry']}")
    spans = Spans()
    entry = module.Entry(config, cell, seed, spans)
    if wrap_entry is not None:
        entry = wrap_entry(entry)
    t_inputs = time.perf_counter()
    warm = []
    for k in range(int(cell.get("warmup_calls", 2))):
        entry.call(data.WARMUP, k)
        warm.append(time.perf_counter())
    spans.clear()
    setup_s = time.perf_counter() - T_START
    log(f"setup {setup_s:.3f} s: jax import {t_import - T_START:.3f}, "
        f"chip {t_chip - t_import:.3f}, program import and inputs "
        f"{t_inputs - t_chip:.3f}, warm-up calls "
        + ", ".join(f"{b - a:.3f}" for a, b in zip([t_inputs] + warm, warm)),
        file=sys.stderr)

    n_keep = int(cell["check_calls"])
    priority = data.rng(seed, data.CHECK).random(MAX_KEEP)
    kept: dict[int, object] = {}
    latencies, failures = [], []
    starts, ends = [], []       # call start offsets; indices completed
    watch = WindowWatch()
    trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_") if trace else None
    if trace:
        # a traced run may record a shorter window (``trace_seconds``) where
        # a call's device program holds tens of thousands of operations
        seconds = min(seconds, float(cell.get("trace_seconds", seconds)))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0     # spans yes, Python calls no
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("chipbench.window"), watch():
            w0 = time.perf_counter()
            i = 0
            while True:
                c0 = time.perf_counter()
                starts.append(c0 - w0)
                try:
                    with spans("call"):
                        out = entry.call(data.WINDOW, i)
                except getattr(module, "FAILURES", ()) as e:
                    failures.append(f"call {i}: {e}")
                else:
                    latencies.append(time.perf_counter() - c0)
                    ends.append(i)
                    # a sample drawn from the seed: the calls of lowest
                    # priority among those completed
                    if i < MAX_KEEP:
                        kept[i] = out
                        if len(kept) > n_keep:
                            kept.pop(max(kept, key=lambda j: priority[j]))
                i += 1
                if time.perf_counter() - w0 >= seconds or i >= MAX_KEEP:
                    break
            window_s = time.perf_counter() - w0
    finally:
        if trace:
            jax.profiler.stop_trace()
    attempted = i

    tr = None
    if trace:
        from chipbench.trace import Trace
        tr = Trace.from_dir(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)

    stats = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": stats.get("peak_bytes_in_use")}
    ctx = types.SimpleNamespace(  # what a metric reader reads
        setup_s=setup_s, window_s=window_s, n_done=len(latencies),
        attempted=attempted, latencies=latencies,
        work_done=entry.work * len(latencies), spans=dict(spans.seconds),
        trace=tr, device_kind=dev.device_kind, entry=entry, cell=cell,
        config=config)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell_metrics(bench, workload, kind):
        v = load_metric(m["name"]).read(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result = {"correct": False, "attempted": attempted,
              "failed": len(failures), "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(),
                               "idle_gaps": tr.idle_gaps()}

    if latencies:
        j = int(np.argmax(latencies))
        log(f"window {window_s:.3f} s: slowest call {ends[j]} at "
            f"{starts[ends[j]]:.3f} s took {latencies[j] * 1e3:.3f} ms; "
            + watch.summary(), file=sys.stderr)
    for name, v in sorted(spans.seconds.items()):
        ms = np.asarray(v) * 1e3
        log(f"span {name}: {len(ms)} calls, ms mean {ms.mean():.3f} "
            f"median {np.median(ms):.3f} p95 {np.quantile(ms, 0.95):.3f} "
            f"max {ms.max():.3f}", file=sys.stderr)
    # the program's state is freed before the reference runs
    entry.close()
    del ctx, tr
    t_check = time.perf_counter()
    readings = [entry.readings(j, kept[j])
                for j in sorted(kept)]
    checks = compare(readings, cell["limits"]) if readings else []
    log(f"checked calls {sorted(kept)} of {attempted} in "
        f"{time.perf_counter() - t_check:.1f} s; compile cache {cache}",
        file=sys.stderr)
    result["correct"] = bool(readings) and not failures \
        and all(c["ok"] for c in checks)
    result["checks"] = {c["name"]: {"value": c["value"],
                                    c["side"]: c["limit"]} for c in checks}
    for f in failures[:5]:
        log(f"failed {f}", file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}; nothing run", file=sys.stderr)
        return 3
    print(f"correct {result['correct']}", file=sys.stderr)
    for name, c in result["checks"].items():
        side = "max" if "max" in c else "min"
        print(f"check {name} = {c['value']!r} ({side} {c[side]!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
