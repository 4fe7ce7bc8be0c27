"""Batched stateful threshold/hysteresis scan as a Pallas TPU kernel.

The fleet backtesting engine needs, for B = N x M x K scenario rows over
an [N, T] table of market price series (row b reads series
``market_idx[b]``), four per-row sums (see
`repro.kernels.ref.FleetScanOut`) driven by a per-row two-threshold
state machine. A naive formulation is a sequential scan over T — hostile
to the VPU. The kernel instead removes the time recurrence *inside* each
block with a last-decisive-event trick:

    on_t  = 0 if p_t > p_off, 1 if p_t <= p_on, else on_{t-1}

is "state of the most recent decisive sample". Encoding each decisive
sample as ev_t = 2 t + on_t (on/off are mutually exclusive since
p_on <= p_off) and taking a running max over time yields, per element, the
index *and* decision of the latest event in one prefix max — no serial
loop; samples before the first event inherit the carry from the previous
block. The prefix max is log-depth shift-and-`maximum` over sublanes
(`kernels.ref.doubling_prefix`): Mosaic has no `cummax` lowering, and
max is exact, so any association gives the same bits.

Layout: time-major [T, B] blocks (rows ride the 128-lane axis, the running
max runs along sublanes). Grid = (n_work_items, n_time_blocks) with time
innermost, so the on/off carry and the four accumulators live in VMEM
scratch across time blocks — zero HBM round-trips for state, exactly the
pattern of `ssd_scan.py`. Padding in T is masked in-kernel against the true
length; padding in B is sliced off by the wrapper.

Prices are read by market index, never copied per row: the wrapper lays
out only the [N, T] table, split exactly into three bfloat16 planes
(`price_planes`), time-major, with the market axis in tiles of 128 lanes
(all three planes of one tile side by side: [n_tiles, T*, 384]). The
grid's outer axis runs over work items, one (row block, market tile)
pair for each tile a row block's markets touch, given by scalar
prefetch; `build_grid`'s market-major rows touch one tile a block, and
rows in any other order are sorted by market first. Each step fetches
the time block of its item's tile only and picks its rows' prices with
one one-hot matmul against a [384, block_b] one-hot built into VMEM
scratch at the item's first time block; rows of other tiles see no
valid sample. Every output of that matmul sums one row's three planes
and zeros, all disjoint bit fields of one float32 price, so any
summation order rebuilds the price bit for bit: the state machine sees
exactly the float32 values a gather would give, at one MXU pass. No
[B, T] array of prices exists in HBM in either layout, and VMEM holds
the same blocks whatever N is.

Validated in interpret mode against `repro.kernels.ref.fleet_scan_ref`
on the gathered rows.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ref import FleetScanOut, doubling_prefix

N_PLANES = 3          # bfloat16 planes that rebuild a float32 exactly
TILE = 128            # markets per lane tile of the table


def price_planes(prices: jax.Array) -> jax.Array:
    """Split float32 ``prices`` exactly into three bfloat16 planes,
    stacked on a new leading axis: ``hi + mid + lo == prices``.

    Each plane keeps the next 8 significant bits by truncation (a bit
    mask, not a rounding convert, so no compiler may fold the split
    away), leaving three disjoint bit fields of each price: every
    partial sum of them, in any order, is exact in float32. Prices must
    be finite; magnitudes below about 1e-33 lose bits to subnormal
    flushing, far below any price."""
    def trunc(x):
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    p = jnp.asarray(prices, jnp.float32)
    hi = trunc(p)
    mid = trunc(p - hi)
    lo = p - hi - mid
    return jnp.stack([hi, mid, lo]).astype(jnp.bfloat16)


def _fleet_kernel(blk_ref, tile_ref, live_ref,                  # prefetch
                  tab_ref, idx_ref, pon_ref, poff_ref, lvl_ref,  # inputs
                  idle_ref,
                  out_ref,                                       # [4, bb]
                  onehot_scr, state_scr, acc_scr,                # scratch
                  *, block_t: int, n_t_blocks: int, t_total: int):
    w, ti = pl.program_id(0), pl.program_id(1)
    live = w < live_ref[0]
    # rows of this row block whose market lies in this item's tile
    local = idx_ref[...] - tile_ref[w] * TILE       # [1, bb]
    in_tile = (local >= 0) & (local < TILE)

    @pl.when(live & (ti == 0) &
             ((w == 0) | (blk_ref[w] != blk_ref[jnp.maximum(w - 1, 0)])))
    def _first_item_of_block():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(live & (ti == 0))
    def _init():
        state_scr[...] = jnp.ones_like(state_scr)   # start running
        # column b picks row b's market in each plane, or nothing when
        # the market lies in another tile
        k = jax.lax.broadcasted_iota(jnp.int32, onehot_scr.shape, 0)
        hit = ((k == local) | (k == local + TILE) | (k == local + 2 * TILE))
        onehot_scr[...] = jnp.where(hit & in_tile, 1.0,
                                    0.0).astype(onehot_scr.dtype)

    @pl.when(live)
    def _scan():
        # [bt, 3*TILE] @ [3*TILE, bb]: each output is hi + mid + lo of one
        # price, or 0 for a row of another tile
        p = jnp.dot(tab_ref[...], onehot_scr[...],
                    preferred_element_type=jnp.float32)  # [bt, bb]
        pon = pon_ref[...]                          # [1, bb]
        poff = poff_ref[...]
        lvl = lvl_ref[...]
        idle = idle_ref[...]

        tloc = jax.lax.broadcasted_iota(jnp.int32, p.shape, 0)
        # T-padding mask; rows of another tile see no valid sample
        valid = (ti * block_t + tloc) < jnp.where(in_tile, t_total, 0)

        on_ev = (p <= pon) & valid
        off_ev = (p > poff) & valid
        # ev = 2t for an off event, 2t+1 for an on event, -1 otherwise;
        # the running max is then the latest decisive event and its low
        # bit the state it imposed.
        ev = jnp.where(on_ev | off_ev,
                       2 * tloc + on_ev.astype(jnp.int32), -1)
        last = doubling_prefix(ev, jnp.maximum, -1, axis=0)  # [bt, bb]

        carry = state_scr[...]                      # [1, bb] in {0, 1}
        on = jnp.where(last >= 0, (last & 1).astype(jnp.float32),
                       carry)                       # [bt, bb]
        on_prev = jnp.concatenate([carry, on[:-1]], axis=0)
        starts = jnp.maximum(on - on_prev, 0.0)     # only at valid samples

        vf = valid.astype(jnp.float32)
        cap = lvl + (1.0 - lvl) * on
        draw = cap + idle * (1.0 - cap)
        acc_scr[...] += jnp.concatenate(
            [jnp.sum(v, axis=0, keepdims=True)
             for v in (draw * p * vf, cap * vf, starts, starts * p)], axis=0)
        # events on invalid samples are masked, so the last row is the
        # state at the last valid sample even in a partially (or fully)
        # padded block.
        state_scr[...] = on[block_t - 1:]

    @pl.when(live & (ti == n_t_blocks - 1))
    def _finish():
        # a row block's later items add to the same output block, which
        # stays in VMEM until the grid moves to another row block
        out_ref[...] = acc_scr[...]


@functools.partial(jax.jit,
                   static_argnames=("block_b", "block_t", "t_total",
                                    "interpret"))
def _fleet_scan_padded(blk: jax.Array, tile: jax.Array, live: jax.Array,
                       tab: jax.Array, idx: jax.Array, pon: jax.Array,
                       poff: jax.Array, lvl: jax.Array, idle: jax.Array, *,
                       block_b: int, block_t: int, t_total: int,
                       interpret: bool) -> jax.Array:
    """Core pallas_call over padded, time-major inputs.

    blk, tile: [W] int32 work items (row block, market tile; see
    `_work_items`), live: [1] int32 count of real items; tab: [n_tiles,
    T*, 3 * TILE] bfloat16 price planes (`_table`); idx: [1, B*] int32
    market of each row; params: [1, B*] (2-D: Mosaic tiles a 1-D operand
    differently from XLA). Returns [4, B*].
    """
    t_pad = tab.shape[1]
    n_items = blk.shape[0]
    nt = t_pad // block_t

    def time_block(w, ti, blk, tile, live):
        # past the real items, stay on the last block fetched: no copy
        return tile[w], jnp.where(w < live[0], ti, nt - 1), 0

    def row_block(w, ti, blk, tile, live):
        return 0, blk[w]

    kernel = functools.partial(_fleet_kernel, block_t=block_t,
                               n_t_blocks=nt, t_total=t_total)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_items, nt),
            in_specs=[pl.BlockSpec((None, block_t, N_PLANES * TILE),
                                   time_block)]
            + [pl.BlockSpec((1, block_b), row_block)] * 5,
            out_specs=pl.BlockSpec((4, block_b), row_block),
            scratch_shapes=[
                pltpu.VMEM((N_PLANES * TILE, block_b), tab.dtype),
                pltpu.VMEM((1, block_b), jnp.float32),
                pltpu.VMEM((4, block_b), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((4, idx.shape[1]), jnp.float32),
        interpret=interpret,
    )(blk, tile, live, tab, idx, pon, poff, lvl, idle)


def _work_items(idx: jax.Array, block_b: int, n_items: int):
    """The grid's work items for padded row indices ``idx`` [B*]: one
    (row block, market tile) pair for each tile between the lowest and
    highest of the block's markets, in row-block order, padded to
    ``n_items`` by repeating the last. Returns (blk, tile, live), live
    being the [1] count of real items; it exceeds ``n_items`` only when
    rows are not grouped by market tile."""
    tiles = idx.reshape(-1, block_b) // TILE
    lo, hi = tiles.min(axis=1), tiles.max(axis=1)
    ends = jnp.cumsum(hi - lo + 1)
    w = jnp.arange(n_items)
    blk = jnp.minimum(jnp.searchsorted(ends, w, side="right"),
                      ends.shape[0] - 1)
    first = ends[blk] - (hi - lo + 1)[blk]
    tile = jnp.minimum(lo[blk] + w - first, hi[blk])
    return blk.astype(jnp.int32), tile.astype(jnp.int32), ends[-1:]


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pick_block(n: int, cap: int) -> int:
    """Largest 128-multiple <= min(cap, n), or n itself for small n."""
    cap = max(min(cap, n), 1)
    return (cap // 128) * 128 if cap >= 128 else cap


def _table(prices: jax.Array, pad_t: int) -> jax.Array:
    """[N, T] prices as [n_tiles, T*, 3 * TILE] time-major bfloat16
    planes: plane j of market TILE * g + l at [g, :, j * TILE + l], so a
    time block of one market tile is one contiguous block."""
    n, t = prices.shape
    n_tiles = -(-n // TILE)
    planes = jnp.pad(price_planes(prices),
                     ((0, 0), (0, n_tiles * TILE - n), (0, pad_t)))
    return (planes.reshape(N_PLANES, n_tiles, TILE, t + pad_t)
            .transpose(1, 3, 0, 2).reshape(n_tiles, t + pad_t, -1))


def _by_market(rows):
    """``rows`` (market index first, then [B] fields) sorted by market,
    and the order that sorts them."""
    order = jnp.argsort(rows[0]).astype(jnp.int32)
    return tuple(v[order] for v in rows), order


def fleet_scan(prices: jax.Array, market_idx: jax.Array, p_on: jax.Array,
               p_off: jax.Array, off_level: jax.Array,
               idle_frac: jax.Array, *, block_b: int = 128,
               block_t: int = 512,
               interpret: Optional[bool] = None) -> FleetScanOut:
    """Batched hysteresis scan. prices: [N, T] finite market series;
    market_idx: [B] int, row -> series; params: [B] (broadcastable).

    Equals `repro.kernels.ref.fleet_scan_ref` on ``prices[market_idx]``,
    which requires ``p_on <= p_off`` (the event encoding gives "on"
    precedence inside an inverted band, the reference gives "off" —
    `repro.fleet.grid` validates this). Rows may come in any order and
    mix markets within a block. Rows grouped by market tile (as
    `repro.fleet.grid.build_grid` makes them, market-major) run as
    given; otherwise, past one tile, they are sorted by market first
    and the results put back, at the cost of a sort and a few
    [B]-sized gathers.
    Each row block reads the time blocks of its own market tiles only,
    so the cost per row does not grow with N. This is the hot inner
    loop of `repro.fleet.engine.backtest`.
    """
    idx = jnp.asarray(market_idx, jnp.int32)
    n, t = jnp.shape(prices)
    b = idx.shape[0]
    block_b = _pick_block(b, block_b)
    block_t = _pick_block(t, block_t)
    pad_b = (-b) % block_b
    pad_t = (-t) % block_t
    n_tiles = -(-n // TILE)
    # enough for rows grouped by tile: each tile boundary adds one item
    n_items = (b + pad_b) // block_b + n_tiles - 1

    def padded(rows):
        # padding rows repeat the last market, so grouping survives
        return ((jnp.pad(rows[0], (0, pad_b), mode="edge"),)
                + tuple(jnp.pad(v, (0, pad_b)) for v in rows[1:]))

    rows = (idx,) + tuple(jnp.broadcast_to(jnp.asarray(v, jnp.float32), (b,))
                          for v in (p_on, p_off, off_level, idle_frac))
    with jax.named_scope("fleet_scan.layout"):
        # only the [N, T] table is laid out, never a [B, T] copy
        tab = _table(jnp.asarray(prices, jnp.float32), pad_t)
        if n_tiles > 1:
            live = _work_items(padded(rows)[0], block_b, n_items)[2]
            grouped = live[0] <= n_items
            # a sort of the index alone: a sort that carries the four
            # parameters along takes minutes to compile for the chip
            rows, order = jax.lax.cond(
                grouped,
                lambda: (rows, jnp.arange(b, dtype=jnp.int32)),
                lambda: _by_market(rows))
        rows = padded(rows)
        blk, tile, live = _work_items(rows[0], block_b, n_items)
    out = _fleet_scan_padded(
        blk, tile, live, tab, *(v[None, :] for v in rows),
        block_b=block_b, block_t=block_t, t_total=t,
        interpret=_auto_interpret(interpret))[:, :b]
    if n_tiles > 1:
        out = jax.lax.cond(grouped, lambda: out,
                           lambda: jnp.zeros_like(out).at[:, order].set(out))
    return FleetScanOut(*out)
