"""Entry ``sweep``: a policy sweep over market-years x systems x
policies through `build_grid` and `backtest`.

One call is what an analyst runs: `build_grid` from the price matrix
held on the device (threshold resolution on the host), `backtest`, and
the per-row results fetched to the host. Each call draws fresh
shutdown shares from the seed and its index, crossed with the
configuration's policy kinds.

Cell parameters: ``years_per_market`` and ``bootstrap`` (market-years),
``psi`` / ``psi_count`` and ``ratings_mw`` (systems, else the
config's), ``shares_per_call``, ``check_calls`` (window calls checked
in full against the reference) and ``limits``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import data
from chipbench.reference import fleet as ref

FIELDS = ("cpc", "tco", "up_hours", "n_starts")


class Entry:
    def __init__(self, config: dict, cell: dict, seed: int, spans):
        from repro.fleet import backtest, build_grid
        self._build_grid, self._backtest = build_grid, backtest
        self.config, self.cell, self.seed, self.spans = \
            config, cell, seed, spans
        self.hours = int(config["hours"])
        self.prices_host = data.market_years(
            config, seed, int(cell["years_per_market"]),
            bool(cell["bootstrap"]))
        self.prices = jax.device_put(self.prices_host)
        self.specs = data.system_specs(config, cell)
        self.p_avg = data.psi_price(config)
        self.systems = data.systems(self.specs, self.hours, self.p_avg)
        self.n_markets = self.prices_host.shape[0]
        self.n_policies = int(cell["shares_per_call"]) \
            * len(config["policy_kinds"])
        self.n_rows = self.prices_host.shape[0] * len(self.specs) \
            * self.n_policies
        self.work = float(self.n_rows * self.hours)

    def policies(self, stream: int, index: int) -> list[dict]:
        xs = data.shares(self.config, self.seed, stream, index,
                         int(self.cell["shares_per_call"]))
        return data.policy_table(self.config, xs)

    def run(self, table: list[dict]) -> dict:
        """The timed path on one policy table: grid, backtest, fetch."""
        with self.spans("grid"):
            grid = self._build_grid(self.prices, self.systems,
                                    data.policy_specs(table))
        self._last_grid = grid
        with self.spans("backtest"):
            rep = self._backtest(grid)
            out = jax.device_get({f: getattr(rep, f) for f in FIELDS})
        return out

    def call(self, stream: int, index: int) -> dict:
        return self.run(self.policies(stream, index))

    def program_bytes(self) -> dict:
        """Compile-time memory of the `backtest` program the calls ran
        (its temporaries, which the device's peak-bytes counter misses).
        It lowers the program's private jitted function, so only
        ``readings.py --program-bytes`` calls it, never a run."""
        from repro.fleet.engine import _backtest_jit
        from repro.obs.profiling import record_compiled
        g = self._last_grid
        compiled = _backtest_jit.lower(
            g.prices, g.market_idx, g.system_idx, g.policy_idx, g.fixed,
            g.power, g.period, g.p_on, g.p_off, g.off_level, g.idle_frac,
            g.restart_energy_mwh, g.restart_time_h,
            use_pallas=jax.default_backend() == "tpu", block_b=128,
            block_t=512).compile()
        return record_compiled("backtest", compiled)

    def close(self) -> None:
        self.prices = self._last_grid = None

    # -- the check ---------------------------------------------------------

    def reference(self, index: int, dtype=np.float64) -> dict:
        """The reference's answer to window call ``index``."""
        systems = [(psi * self.hours * mw * self.p_avg, mw, self.hours)
                   for psi, mw in self.specs]
        return ref.sweep(self.prices_host, systems,
                         self.policies(data.WINDOW, index), dtype)

    def control(self, index: int, out: dict) -> dict:
        """The reference in bfloat16, in the program's place."""
        return self.reference(index, ref.BF16)

    def readings(self, index: int, out: dict) -> dict:
        """Numbers compared with the reference's answer to call
        ``index``: the widest relative gap of each cost field and the
        widest gap in start counts, over every row."""
        want = self.reference(index)
        got = {f: np.asarray(out[f], np.float64) for f in FIELDS}
        exp = {f: np.asarray(want[f], np.float64) for f in FIELDS}
        rel = lambda f: float(np.max(np.abs(got[f] - exp[f])  # noqa: E731
                                     / np.abs(exp[f])))
        return {"cpc_rel": rel("cpc"), "tco_rel": rel("tco"),
                "up_hours_rel": rel("up_hours"),
                "starts_gap": float(np.max(np.abs(got["n_starts"]
                                                  - exp["n_starts"])))}


# -- faults planted under the timed path ---------------------------------


def _alter_first(rep, field):
    v = getattr(rep, field)
    return rep._replace(**{field: v.at[0].multiply(1.01)})


def _half_rows(rep):
    """Half of the rows left out: their answers are the mean over the
    rest."""
    half = rep.cpc.shape[0] // 2
    return rep._replace(**{
        f: getattr(rep, f).at[half:].set(jnp.mean(getattr(rep, f)[:half]))
        for f in FIELDS})


def _backtest_fault(alter):
    def plant(entry):
        real = entry._backtest
        entry._backtest = lambda g: alter(real(g))
        return entry
    return plant


FAULTS = {
    # the first row's cost per compute off by 1 %
    "answer_altered": _backtest_fault(lambda rep: _alter_first(rep, "cpc")),
    "half_batch": _backtest_fault(_half_rows),
}
