"""Entry ``tune``: `optimize` with the library's default `TuneConfig`
(300 annealed Adam steps, hard re-evaluation and selection) on a grid of
market-years x systems x policies.

One call: `build_grid` from the prices held on the device with fresh
shutdown shares drawn from the seed and the call's index, `optimize`,
and the selected per-row policies and their cost per compute fetched to
the host.

The check evaluates, in float64, every row's selected policy under the
row's own market, system and hardware, and compares the cost per compute
`optimize` reports (``cpc_rel``). It also compares the selection with the
best swept policy of the row's cell under the row's hardware: the mean
gain (``gain_pct``) is what the tuning adds, and an optimizer that
leaves its policies where they started gains nothing. The rows of one
group (market-year, system, hardware) are restarts of one problem, and
`optimize` keeps the swept policy where a restart's tuning does not beat
it; so the share of groups whose best selected policy beats the group's
best swept one by ``TUNED_MIN_GAIN`` (``tuned_groups_pct``) falls with
the share of rows the tuning reaches, which the mean gain hides.

Cell parameters as for ``sweep``, and ``limits``.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data
from chipbench.entries import sweep
from chipbench.reference import fleet as ref

# the relative gain over the best swept policy that counts a group as
# tuned: above the float32 rounding of the swept thresholds, far below
# what a tuned restart gains
TUNED_MIN_GAIN = 1e-4


class Entry(sweep.Entry):
    """The sweep's inputs; the call tunes instead of backtesting."""

    def __init__(self, config: dict, cell: dict, seed: int, spans):
        super().__init__(config, cell, seed, spans)
        from repro.tune import TuneConfig, optimize
        self._optimize, self._tune_config = optimize, TuneConfig()
        self.work = float(self.n_rows)

    def run(self, table: list[dict]) -> dict:
        with self.spans("grid"):
            grid = self._build_grid(self.prices, self.systems,
                                    data.policy_specs(table))
        self._last_grid = grid
        with self.spans("optimize"):
            res = self._optimize(grid, self._tune_config)
            out = jax.device_get({"cpc": res.cpc, "p_on": res.params.p_on,
                                  "p_off": res.params.p_off,
                                  "off_level": res.params.off_level})
        return out

    def program_bytes(self) -> dict:
        """Compile-time memory of the tuning loop the calls ran (see
        `sweep.Entry.program_bytes`)."""
        from repro.obs.profiling import record_compiled
        from repro.tune import init_from_grid, problem_from_grid, tune_loop
        g = self._last_grid
        compiled = tune_loop.lower(init_from_grid(g), problem_from_grid(g),
                                   None, cfg=self._tune_config,
                                   telemetry=False).compile()
        return record_compiled("tune_loop", compiled)

    # -- the check ---------------------------------------------------------

    def _rows(self, table):
        """Per-row inputs in grid order b = (n * M + m) * K + k."""
        n, m, k = self.n_markets, len(self.specs), len(table)
        sys_ = np.asarray([(psi * self.hours * mw * self.p_avg, mw)
                           for psi, mw in self.specs])
        pol = lambda key: np.tile([q[key] for q in table], n * m)  # noqa: E731
        return {
            "market": np.repeat(np.arange(n), m * k),
            "fixed": np.tile(np.repeat(sys_[:, 0], k), n),
            "power": np.tile(np.repeat(sys_[:, 1], k), n),
            "idle_frac": pol("idle_frac"),
            "restart_energy_mwh": pol("restart_energy_mwh"),
            "restart_time_h": pol("restart_time_h"),
        }

    def _cpc(self, rows, p_on, p_off, off_level, dtype=np.float64):
        p = self.prices_host[rows["market"]]
        sums = ref.run_rows(p, p_on, p_off, off_level, rows["idle_frac"],
                            dtype)
        return ref.costs(sums, fixed=rows["fixed"], power=rows["power"],
                         period=np.full(len(p), float(self.hours)),
                         restart_energy_mwh=rows["restart_energy_mwh"],
                         restart_time_h=rows["restart_time_h"],
                         price_sum=np.asarray(p, np.float64).sum(axis=1),
                         hours=self.hours, dtype=dtype)["cpc"]

    def _best_swept(self, table, rows) -> np.ndarray:
        """[B] float64: the lowest cost per compute of any swept policy
        of the row's (market, system) cell, under the row's hardware
        (idle draw and restart of the row's own policy kind)."""
        n, m, k = self.n_markets, len(self.specs), len(table)
        p_off, p_on = ref.thresholds(self.prices_host, table)   # [N, K]
        col = lambda key: np.array([q[key] for q in table])  # noqa: E731
        # the state machine of candidate kk with the idle draw of kind
        # k, per market: index (n, kk, k)
        nn, kk, ki = (a.reshape(-1) for a in np.meshgrid(
            np.arange(n), np.arange(k), np.arange(k), indexing="ij"))
        sums = ref.run_rows(self.prices_host[nn], p_on[nn, kk],
                            p_off[nn, kk], col("off_level")[kk],
                            col("idle_frac")[ki])
        sums = [s.reshape(n, 1, k, k) for s in sums]      # [N, 1, K', K]
        shape = (n, m, k, k)
        b = lambda v: np.broadcast_to(  # noqa: E731  row field -> (n,m,k',k)
            np.asarray(v).reshape(n, m, 1, k), shape)
        price_sum = np.asarray(self.prices_host, np.float64).sum(axis=1)
        cpc = ref.costs([np.broadcast_to(s, shape) for s in sums],
                        fixed=b(rows["fixed"]), power=b(rows["power"]),
                        period=float(self.hours),
                        restart_energy_mwh=b(rows["restart_energy_mwh"]),
                        restart_time_h=b(rows["restart_time_h"]),
                        price_sum=b(price_sum[rows["market"]]),
                        hours=self.hours)["cpc"]
        return cpc.min(axis=2).reshape(-1)

    def readings(self, index: int, out: dict) -> dict:
        table = self.policies(data.WINDOW, index)
        rows = self._rows(table)
        want = self._cpc(rows, out["p_on"], out["p_off"], out["off_level"])
        got = np.asarray(out["cpc"], np.float64)
        best = self._best_swept(table, rows)
        gain = 1.0 - want / best
        # groups: rows alike in market-year, system and hardware; the
        # best swept policy is the same for every row of a group
        key = np.stack([rows["market"], rows["fixed"], rows["power"],
                        rows["idle_frac"], rows["restart_energy_mwh"],
                        rows["restart_time_h"]], axis=1)
        group = np.unique(key, axis=0, return_inverse=True)[1].reshape(-1)
        group_gain = np.full(group.max() + 1, -np.inf)
        np.maximum.at(group_gain, group, gain)
        return {"cpc_rel": float(np.max(np.abs(got - want) / np.abs(want))),
                "gain_pct": float(100.0 * np.mean(gain)),
                "tuned_groups_pct": float(
                    100.0 * np.mean(group_gain >= TUNED_MIN_GAIN))}

    def control(self, index: int, out: dict) -> dict:
        """The program's selected policies, costed by the reference in
        bfloat16 in the program's place."""
        rows = self._rows(self.policies(data.WINDOW, index))
        return {**out, "cpc": self._cpc(rows, out["p_on"], out["p_off"],
                                        out["off_level"], ref.BF16)}


# -- faults planted under the timed path ---------------------------------


def _optimize_fault(alter):
    """`optimize`'s result with its per-row cost per compute altered."""
    def plant(entry):
        real = entry._optimize

        def altered(grid, cfg):
            res = real(grid, cfg)
            cpc = np.array(res.cpc)
            alter(cpc)
            return res._replace(cpc=cpc)

        entry._optimize = altered
        return entry
    return plant


def _alter_first(cpc):
    cpc[0] *= 1.01


def _half_rows(cpc):
    """Half of the rows left out: their answers are the mean over the
    rest."""
    half = len(cpc) // 2
    cpc[half:] = cpc[:half].mean()


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


def _loop_fault(keep_rows):
    def plant(entry):
        real = entry._optimize

        def broken(grid, cfg):
            with _tune_loop_replaced(keep_rows):
                return real(grid, cfg)

        entry._optimize = broken
        return entry
    return plant


FAULTS = {
    # the first row's cost per compute off by 1 %
    "answer_altered": _optimize_fault(_alter_first),
    "half_batch": _optimize_fault(_half_rows),
    # the tuning loop returns the state it was given
    "state_unchanged": _loop_fault(np.arange),
    # the update reaches only the first half of the rows
    "half_update": _loop_fault(lambda n: np.arange(n // 2, n)),
}
