"""Inputs of every cell, made from the seed: market-years, systems and
shutdown policies, built through the program's public constructors.

Every draw comes from `numpy.random.default_rng` keyed by the run's
seed (any whole number, 64-bit and beyond) and a stream tag, so the
same seed gives the same inputs and a call's policies depend only on
the seed and the call's index.
"""

from __future__ import annotations

import numpy as np

SEED_CAP = 2 ** 31 - 1     # the market generator takes an int32 seed


def rng(seed: int, *stream: int) -> np.random.Generator:
    """Generator for one named stream of a run's seed."""
    return np.random.default_rng([int(seed), *map(int, stream)])


# stream tags
MARKETS, WARMUP, WINDOW, CHECK = 0, 1, 2, 3


def market_years(config: dict, seed: int, years_per_market: int,
                 bootstrap: bool):
    """[n_markets * years_per_market, hours] float32 host prices.

    One base year per calibrated market preset is generated from the
    seed; with ``bootstrap`` each is resampled by the weekly moving-block
    bootstrap into ``years_per_market`` market-years, otherwise the base
    years themselves are used (``years_per_market`` must then be 1).
    Rows are market-major: all years of the first market, then the next.
    """
    from repro.energy.ensemble import block_bootstrap
    from repro.energy.markets import generate_market
    from repro.energy.presets import region_params

    r = rng(seed, MARKETS)
    hours = int(config["hours"])
    out = []
    for region in config["markets"]:
        gen_seed, boot_seed = (int(v) for v in r.integers(0, SEED_CAP, 2))
        mp = region_params(region, seed=gen_seed).replace(n_hours=hours)
        base = np.asarray(generate_market(mp).prices, np.float32)
        if bootstrap:
            out.append(block_bootstrap(base, years_per_market,
                                       block_hours=int(config["block_hours"]),
                                       seed=boot_seed))
        else:
            if years_per_market != 1:
                raise ValueError("without the bootstrap each market has "
                                 "one base year")
            out.append(base[None, :])
    return np.concatenate(out, axis=0)


def psi_price(config: dict) -> float:
    """Average price (EUR/MWh) that turns Psi into a fixed cost:
    F = Psi * hours * MW * p_avg, at the preset the config names."""
    from repro.energy.presets import region_params
    return float(region_params(config["psi_market"]).p_avg)


def system_specs(config: dict, cell: dict) -> list[tuple[float, float]]:
    """(Psi, MW) of every system, Psi-major: the cell's own lists where it
    gives them, else the config's geometric Psi range x its ratings."""
    psis = cell.get("psi")
    if psis is None:
        lo, hi, n = config["psi_range"]
        psis = np.geomspace(lo, hi, int(cell.get("psi_count", n))).tolist()
    ratings = cell.get("ratings_mw", config["ratings_mw"])
    return [(float(p), float(mw)) for p in psis for mw in ratings]


def systems(specs, hours: int, p_avg: float):
    from repro.core.tco import make_system
    return [make_system(psi * hours * mw * p_avg, mw, float(hours))
            for psi, mw in specs]


def shares(config: dict, seed: int, stream: int, index: int, n: int):
    """``n`` shutdown shares drawn from the config's range for one call."""
    lo, hi = config["shutdown_share_range"]
    return np.sort(rng(seed, stream, index).uniform(lo, hi, n))


POLICY_DEFAULTS = {"hysteresis": 1.0, "off_level": 0.0, "idle_frac": 0.0,
                   "restart_energy_mwh": 0.0, "restart_time_h": 0.0}


def policy(fields: dict) -> dict:
    """Every `PolicySpec` field of one policy (x, hysteresis, off_level,
    idle_frac, restart_energy_mwh, restart_time_h), defaults filled."""
    return {**POLICY_DEFAULTS, **fields}


def policy_table(config: dict, xs) -> list[dict]:
    """Shares x the config's policy kinds, share-major."""
    return [policy({**kind, "x": float(x)})
            for x in xs for kind in config["policy_kinds"]]


def policy_specs(table):
    from repro.fleet import PolicySpec
    return [PolicySpec(f"p{i}", **row) for i, row in enumerate(table)]
