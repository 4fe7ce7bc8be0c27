"""Profiling capture for kernel callers: spans on the device trace's
clock, and XLA cost/memory analyses of compiled programs landing in the
run trace (and therefore the same artifact schema `benchmarks/` writes).

The program never starts a profiler session: whoever runs it does (a
benchmark, or an operator with `jax.profiler.trace`), and the spans
land in that session beside the device's operations.
"""

from __future__ import annotations

import time
from contextlib import contextmanager

import jax

from .registry import current, trace_event


@contextmanager
def profiled(label: str, **attrs):
    """A span named ``repro.<label>``: a `jax.profiler.TraceAnnotation`,
    whose keyword attributes (e.g. rows=..., bytes=...) become the
    event's stats in a profiler session, so a trace reads the span on
    the same clock as the device's operations. With no session active
    a span only checks for one (about 2.5 us an enter on a TPU v5e
    host). When a run is active the span is also timed on the host and
    emitted as a ``profile.span`` event carrying the same attributes."""
    with jax.profiler.TraceAnnotation(f"repro.{label}", **attrs):
        if current() is None:
            yield
            return
        t0 = time.perf_counter()
        try:
            yield
        finally:
            trace_event("profile.span",
                        {"label": label,
                         "seconds": time.perf_counter() - t0, **attrs})


def record_compiled(label: str, compiled) -> dict:
    """Capture `cost_analysis` + `memory_analysis` of a lowered-and-
    compiled jax program into a ``profile.xla`` event; returns the
    payload so benchmark code can also fold it into its artifact JSON.
    Works whether or not a run is active."""
    from repro.launch.hlo_analysis import raw_cost_analysis

    payload: dict = {"label": label}
    try:
        ca = raw_cost_analysis(compiled)
    except Exception:
        ca = {}
    for key, out in (("flops", "flops"),
                     ("bytes accessed", "bytes_accessed"),
                     ("transcendentals", "transcendentals")):
        if key in ca:
            payload[out] = float(ca[key])
    try:
        mem = compiled.memory_analysis()
    except Exception:
        mem = None
    if mem is not None:
        for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                     "output_size_in_bytes", "generated_code_size_in_bytes"):
            val = getattr(mem, attr, None)
            if val is not None:
                payload[attr.replace("_size_in_bytes", "_bytes")] = int(val)
    trace_event("profile.xla", payload)
    return payload
