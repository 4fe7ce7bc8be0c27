"""Operations and bytes the fleet backtest needs, from the problem's
shapes alone: B rows x T hours of the shutdown state machine over N
market price series.

Per row-hour the state machine as stated (`hard_hour_step`):

    on    = p > p_off ? 0 : (p <= p_on ? 1 : on_prev)   2 compares, 2 selects
    start = max(on - on_prev, 0)                        2
    cap   = lvl + (1 - lvl) * on                        3
    draw  = cap + idle * (1 - cap)                      3
    sums  += draw * p, cap, start, start * p            6 (4 adds, 2 muls)

18 operations. Bytes: the [N, T] prices read once, the [B] policy and
system inputs (thresholds, levels, idle draw, fixed cost, power,
period, restart energy and time: 9 floats) and the per-row outputs
(cpc, tco, up hours, starts and the rest of the report: 12 floats).
A per-row copy of the prices is the implementation's choice and does
not count.
"""

OPS_PER_ROW_HOUR = 18
ROW_IN_FLOATS = 9
ROW_OUT_FLOATS = 12


def work(n_rows: int, n_hours: int, n_markets: int) -> tuple[float, float]:
    """(operations, bytes) of one backtest call."""
    ops = float(OPS_PER_ROW_HOUR) * n_rows * n_hours
    nbytes = 4.0 * (n_markets * n_hours
                    + n_rows * (ROW_IN_FLOATS + ROW_OUT_FLOATS))
    return ops, nbytes
