"""Faults planted underneath a cell's timed path, to show that the
numbers a run compares catch them: each wraps an entry and breaks the
program call it makes, at any size.

``chipbench/tests/test_faults.py`` runs every one on the CPU at a tiny
size; ``chipbench/readings.py --fault <kind>`` reads one on the chip at
a cell's own size.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np


def _alter_first(rep, field):
    v = getattr(rep, field)
    return rep._replace(**{field: v.at[0].multiply(1.01)})


def _half_rows(rep):
    """Half of the rows left out: their answers are the mean over the
    rest."""
    half = rep.cpc.shape[0] // 2
    return rep._replace(**{
        f: getattr(rep, f).at[half:].set(jnp.mean(getattr(rep, f)[:half]))
        for f in ("cpc", "tco", "up_hours", "n_starts")})


def sweep(kind):
    def wrap(entry):
        real = entry._backtest
        entry._backtest = {
            "answer_altered": lambda g: _alter_first(real(g), "cpc"),
            "half_batch": lambda g: _half_rows(real(g)),
        }[kind]
        return entry
    return wrap


@contextlib.contextmanager
def _tune_loop_replaced(keep_rows):
    """`optimize`'s tuning loop runs as written, then every row that
    ``keep_rows(n)`` selects gets back the parameters it started from
    (and the loop's hard cost of them), as if the update never reached
    it."""
    from repro.tune import optimizer
    loop = optimizer.tune_loop

    def partial(raw0, problem, coupling=None, *, cfg, telemetry=False):
        start = jax.tree.map(jnp.array, raw0)   # the loop donates raw0
        raw_f, hist, _ = loop(raw0, problem, coupling, cfg=cfg,
                              telemetry=telemetry)
        n = start.raw_off.shape[0]
        kept = jnp.zeros(n, bool).at[keep_rows(n)].set(True)
        raw = jax.tree.map(lambda a, b: jnp.where(kept, a, b), start, raw_f)
        phys = optimizer.transform(raw)
        return raw, hist, optimizer.hard_cpc(
            phys.p_on, phys.p_off, phys.off_level, problem)

    optimizer.tune_loop = partial
    try:
        yield
    finally:
        optimizer.tune_loop = loop


TUNE_LOOP_FAULTS = {
    # the tuning loop returns the state it was given
    "state_unchanged": lambda n: np.arange(n),
    # the update reaches only the first half of the rows
    "half_update": lambda n: np.arange(n // 2, n),
}


def tune(kind):
    def wrap(entry):
        real = entry._optimize

        def altered(grid, cfg):
            res = real(grid, cfg)
            cpc = np.array(res.cpc)
            if kind == "answer_altered":
                cpc[0] *= 1.01
            else:
                half = len(cpc) // 2
                cpc[half:] = cpc[:half].mean()
            return res._replace(cpc=cpc)

        def loop_fault(grid, cfg):
            with _tune_loop_replaced(TUNE_LOOP_FAULTS[kind]):
                return real(grid, cfg)

        entry._optimize = loop_fault if kind in TUNE_LOOP_FAULTS \
            else altered
        return entry
    return wrap


# the faults each cell's entry can have
BY_ENTRY = {
    "sweep": (sweep, ("answer_altered", "half_batch")),
    "tune": (tune, ("answer_altered", "half_batch", "state_unchanged",
                    "half_update")),
}
