"""Plain reference of the fleet backtest: threshold resolution (Eq. 1),
the hourly shutdown state machine, and the cost closed form (Eqs. 6, 11).

Written from the paper and the documented row semantics, in NumPy,
importing nothing of the program. ``dtype`` is the precision every
quantity is computed in: float64 for the reference, bfloat16 for the
control (the step below the program's float32).

Prices and thresholds are float32 data in the program (the grid stores
them so); the reference resolves each threshold in float64 and keeps
the float32 value, so both sides compare the same numbers hour by hour.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

BF16 = np.dtype(ml_dtypes.bfloat16)


def thresholds(prices: np.ndarray, policies: list[dict]):
    """Shutdown (p_off) and resume (p_on) prices, [N, K] float32.

    p_off is the price of the ``m``-th most expensive hour of the market,
    m = round(x * T) clipped to [1, T - 1] (the hours a share x of the
    year shuts down); x <= 0 never shuts down (+inf). The resume price
    backs off by (1 - hysteresis) of the threshold's magnitude.
    """
    p = np.asarray(prices, np.float64)
    n, t = p.shape
    desc = -np.sort(-p, axis=1)
    p_off = np.full((n, len(policies)), np.inf)
    p_on = np.full((n, len(policies)), np.inf)
    for k, pol in enumerate(policies):
        if pol["x"] <= 0.0:
            continue
        m = min(max(int(round(pol["x"] * t)), 1), t - 1)
        off = desc[:, m - 1]
        p_off[:, k] = off
        p_on[:, k] = off - (1.0 - pol["hysteresis"]) * np.abs(off)
    return p_off.astype(np.float32), p_on.astype(np.float32)


def run_rows(prices: np.ndarray, p_on, p_off, off_level, idle_frac,
             dtype=np.float64):
    """Hour-by-hour state machine over R independent rows.

    prices: [R, T]; the rest [R]. A row starts running; each hour it
    stops if the price is above p_off, resumes if at or below p_on, and
    otherwise keeps its state. While stopped it keeps ``off_level`` of
    its capacity, and the stopped part draws ``idle_frac``. Returns the
    four sums over the year: price-weighted draw, capacity, starts, and
    price at the starts.
    """
    dt = np.dtype(dtype)
    p = np.asarray(prices).astype(dt)
    p_on, p_off, lvl, idle = (np.asarray(v).astype(dt)
                              for v in (p_on, p_off, off_level, idle_frac))
    r, t = p.shape
    one, zero = dt.type(1.0), dt.type(0.0)
    on = np.ones(r, dt)
    draw_price = np.zeros(r, dt)
    capacity = np.zeros(r, dt)
    starts = np.zeros(r, dt)
    start_price = np.zeros(r, dt)
    for h in range(t):
        ph = p[:, h]
        new = np.where(ph > p_off, zero, np.where(ph <= p_on, one, on))
        start = np.maximum(new - on, zero)
        cap = lvl + (one - lvl) * new
        draw = cap + idle * (one - cap)
        draw_price = draw_price + draw * ph
        capacity = capacity + cap
        starts = starts + start
        start_price = start_price + start * ph
        on = new
    return draw_price, capacity, starts, start_price


def costs(sums, *, fixed, power, period, restart_energy_mwh,
          restart_time_h, price_sum, hours: int, dtype=np.float64):
    """Per-row cost closed form over the four sums: running energy at
    the hourly draw (Eq. 6 with shutdowns), restart energy, lost restart
    time, TCO and cost per compute (TCO over productive hours, Eq. 11's
    form). Returns a dict of [R] arrays."""
    dt = np.dtype(dtype)
    draw_price, capacity, starts, start_price = (np.asarray(s).astype(dt)
                                                 for s in sums)
    fixed, power, period, e_rs, t_rs, price_sum = (
        np.asarray(v).astype(dt) for v in
        (fixed, power, period, restart_energy_mwh, restart_time_h,
         price_sum))
    step_h = period / dt.type(hours)
    energy = step_h * power * draw_price
    up_hours = step_h * capacity - t_rs * starts
    tco = fixed + energy + e_rs * start_price
    cpc = tco / up_hours
    cpc_always_on = (fixed + step_h * power * price_sum) / period
    return {"cpc": cpc, "tco": tco, "up_hours": up_hours,
            "n_starts": starts, "energy_cost": energy,
            "cpc_always_on": cpc_always_on}


def sweep(prices: np.ndarray, systems: list[tuple[float, float, float]],
          policies: list[dict], dtype=np.float64) -> dict:
    """Every row of the market x system x policy sweep, ordered
    b = (n * M + m) * K + k. ``systems`` holds (fixed, MW, period).

    The state machine depends on the market and the policy only, so it
    runs once per (market, policy) and the costs are assembled per row.
    """
    n, t = prices.shape
    m_n, k_n = len(systems), len(policies)
    p_off, p_on = thresholds(prices, policies)
    lvl = np.array([pol["off_level"] for pol in policies])
    idle = np.array([pol["idle_frac"] for pol in policies])
    rows_p = np.repeat(prices, k_n, axis=0)            # [N*K, T]
    sums = run_rows(rows_p, p_on.reshape(-1), p_off.reshape(-1),
                    np.tile(lvl, n), np.tile(idle, n), dtype)
    # broadcast (n, k) sums onto (n, m, k) rows
    sums = [np.broadcast_to(s.reshape(n, 1, k_n), (n, m_n, k_n)).reshape(-1)
            for s in sums]
    sys_arr = np.asarray(systems, np.float64)          # [M, 3]
    pol = lambda key: np.tile(np.array([q[key] for q in policies]), n * m_n)  # noqa: E731
    sysf = lambda j: np.tile(np.repeat(sys_arr[:, j], k_n), n)  # noqa: E731
    price_sum = np.repeat(np.asarray(prices, np.float64).sum(axis=1),
                          m_n * k_n)
    return costs(sums, fixed=sysf(0), power=sysf(1), period=sysf(2),
                 restart_energy_mwh=pol("restart_energy_mwh"),
                 restart_time_h=pol("restart_time_h"),
                 price_sum=price_sum, hours=t, dtype=dtype)
