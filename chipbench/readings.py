#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from: the program's
and the control's, seed by seed, at a cell's own size.

    python3 chipbench/readings.py --workload <cell> --calls <n> --seeds <s> ...

For each seed, in one process: the cell's inputs, ``n`` window calls
through the entry (the program's timed path), a sample of them drawn
from the seed as a run draws it, and for each sampled call the numbers
the run compares, once for the program's answer and once for the
control's (the reference computed in bfloat16, in the program's place).
One JSON line per seed gives the worst of each number over the sampled
calls. Benchmark runs never run the control.

``--fault <kind>`` plants one of the faults of the cell's entry (the
entry module's ``FAULTS``) under the timed path and reads the program so
broken. ``--program-bytes`` prints, after the first seed's calls, the
compile-time memory of the program the calls ran (its temporaries, which
the device's peak-bytes counter misses); it lowers the program's private
jitted functions, so benchmark runs never do it.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chipbench import data, run  # noqa: E402


def seed_readings(workload: str, seed: int, calls: int, *,
                  config_over: dict | None = None,
                  cell_over: dict | None = None, wrap_entry=None,
                  bench: dict | None = None, fault: str | None = None,
                  program_bytes: bool = False) -> dict:
    bench = bench if bench is not None else run.load_json(
        run.ROOT / "BENCHMARK.json")
    _, config, cell = run.load_cell(bench, workload, config_over, cell_over)
    module = importlib.import_module(f"chipbench.entries.{cell['entry']}")
    entry = module.Entry(config, cell, seed, run.Spans())
    if fault is not None:
        faults = getattr(module, "FAULTS", {})
        if fault not in faults:
            raise KeyError(f"entry {cell['entry']!r} has no fault {fault!r}; "
                           f"it has {sorted(faults)}")
        entry = faults[fault](entry)
    if wrap_entry is not None:
        entry = wrap_entry(entry)
    outs = {i: entry.call(data.WINDOW, i) for i in range(calls)}
    memory = entry.program_bytes() if program_bytes else None
    entry.close()
    priority = data.rng(seed, data.CHECK).random(calls)
    sample = sorted(sorted(outs, key=lambda j: priority[j])
                    [:int(cell["check_calls"])])
    prog = run.compare([entry.readings(j, outs[j]) for j in sample],
                       cell["limits"])
    ctrl = run.compare([entry.readings(j, entry.control(j, outs[j]))
                        for j in sample], cell["limits"])
    return {"seed": seed, "calls": sample, "fault": fault,
            **({"program_bytes": memory} if program_bytes else {}),
            "program": {c["name"]: c["value"] for c in prog},
            "program_ok": all(c["ok"] for c in prog),
            "control": {c["name"]: c["value"] for c in ctrl},
            "control_ok": all(c["ok"] for c in ctrl)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--calls", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--program-bytes", action="store_true")
    args = ap.parse_args(argv)
    try:
        run.find_chips(1)
    except run.NoChip as e:
        print(f"readings: {e}", file=sys.stderr)
        return 3
    run.use_compile_cache()
    for j, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        out = seed_readings(args.workload, seed, args.calls,
                            fault=args.fault,
                            program_bytes=args.program_bytes and j == 0)
        out["seconds"] = time.perf_counter() - t0
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
