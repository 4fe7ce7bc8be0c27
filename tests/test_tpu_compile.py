"""Every Pallas kernel of the main path, compiled for a TPU v5e at real
widths by Mosaic (the TPU Pallas compiler).

Nothing runs: the chip is described, not attached (`jax.experimental.
topologies`), so these tests catch what interpret mode cannot — block
shapes that break the (8, 128) tiling rule, primitives Mosaic does not
lower, gathers it refuses, blocks that overflow VMEM — at no chip time.
Each test passes ``interpret=False`` explicitly (the backend here is the
CPU, so the wrappers would otherwise pick interpret mode) and asserts the
kernel is in the compiled program as a ``tpu_custom_call``.

The topology is described inside a module-scope fixture, never while the
module is imported: only one process may load the TPU library, and test
workers import every test file.
"""

import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.fleet.engine import _backtest_jit
from repro.kernels import fleet_scan as fleet_scan_module
from repro.kernels import ops
from repro.kernels.dispatch_scan import dispatch_scan
from repro.kernels.fleet_scan import fleet_scan
from repro.kernels.soft_dispatch import soft_dispatch_fused, \
    soft_dispatch_pallas
from repro.kernels.soft_scan_vjp import soft_state_fused

B, T, S = 1024, 8760, 64          # scenario rows, hourly year, sites


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache off meanwhile."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                     # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def shape(one_chip):
    def make(*dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    return make


def _assert_mosaic(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


# the sweep cell's widths (96 market-years, 98,304 rows), whatif's
# (3 markets, 96 rows in one mixed block), and 2,500 markets: 20 lane
# tiles, more than a VMEM-resident market axis could hold
FLEET_SWEEP = (96, 98_304, T)


def test_fleet_scan_compiles(shape):
    for markets, rows in (FLEET_SWEEP[:2], (3, 96), (2_500, 4_096)):
        _assert_mosaic(
            lambda p, i, a, b, c, d: fleet_scan(p, i, a, b, c, d,
                                                interpret=False),
            shape(markets, T), shape(rows, dtype=jnp.int32),
            *(shape(rows) for _ in range(4)))


def test_backtest_holds_no_per_row_prices(shape, monkeypatch):
    """The Pallas backtest at the sweep's widths reads prices by market
    index: no [B, T] (or time-major [T*, B*]) float32 copy of them, which
    alone would take 3.4 GB, comes back into the program."""
    # the backend here is the CPU: steer the wrapper off interpret mode
    monkeypatch.setattr(fleet_scan_module, "_auto_interpret",
                        lambda interpret: False)
    n, b, t = FLEET_SWEEP
    compiled = _backtest_jit.lower(
        shape(n, t), *(shape(b, dtype=jnp.int32) for _ in range(3)),
        *(shape(b) for _ in range(9)),
        use_pallas=True, block_b=128, block_t=512).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    memory = compiled.memory_analysis()
    if memory is not None:
        assert memory.temp_size_in_bytes < 512 * 2 ** 20
    for dims in re.findall(r"f32\[([\d,]+)\]", text):
        size = math.prod(int(d) for d in dims.split(","))
        assert size < b * t, dims


# the one-hot gathers hold [3S, 3S] in VMEM; S = 256 is the largest
# power of two at which the soft kernels still fit
@pytest.mark.parametrize("sites,hours,block_t",
                         [(S, T, 512), (S, 1000, 100), (256, T, 512)])
def test_dispatch_scan_compiles(shape, sites, hours, block_t):
    _assert_mosaic(
        lambda a, o, r, d: dispatch_scan(a, o, r, d, min_dwell=2,
                                         block_t=block_t, interpret=False),
        shape(sites, hours), shape(hours, 3 * sites, dtype=jnp.int32),
        shape(hours, 3 * sites, dtype=jnp.int32), shape(hours))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
def test_soft_scan_vjp_compiles(shape, direction):
    def state(p, a, b):
        return soft_state_fused(p, a, b, tau=2.0, use_pallas=True,
                                interpret=False)

    fn = state if direction == "fwd" else jax.grad(
        lambda p, a, b: jnp.sum(state(p, a, b)), argnums=(0, 1, 2))
    _assert_mosaic(fn, shape(B, T), shape(B), shape(B))


@pytest.mark.parametrize("sites", [S, 256])
@pytest.mark.parametrize("form", ["pallas", "fused_fwd", "fused_bwd"])
def test_soft_dispatch_compiles(shape, form, sites):
    def fused(a, k, o, d):
        return soft_dispatch_fused(a, k, o, d, tau=1.0, min_dwell=2,
                                   use_pallas=True, interpret=False)

    fn = {
        "pallas": lambda a, k, o, d: soft_dispatch_pallas(
            a, k, o, d, tau=1.0, min_dwell=2, interpret=False),
        "fused_fwd": fused,
        "fused_bwd": jax.grad(lambda a, k, o, d: jnp.sum(fused(a, k, o, d)
                                                         ** 2),
                              argnums=(0, 1, 3)),
    }[form]
    _assert_mosaic(fn, shape(sites, T), shape(T, 3 * sites),
                   shape(T, 3 * sites, dtype=jnp.int32), shape(T))


def test_flash_attention_compiles(shape):
    cfg = get_config("qwen1.5-0.5b")
    h, dh, n = cfg.n_heads, cfg.resolved_head_dim, 2048
    qkv = [shape(1, n, h, dh, dtype=jnp.bfloat16) for _ in range(3)]
    _assert_mosaic(lambda q, k, v: ops.flash_attention(q, k, v,
                                                       interpret=False),
                   *qkv)


def test_decode_attention_compiles(shape):
    cfg = get_config("qwen1.5-0.5b")
    h, g, dh, w = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, 2048
    _assert_mosaic(
        lambda q, k, v, m: ops.decode_attention(q, k, v, m,
                                                interpret=False),
        shape(8, 1, h, dh, dtype=jnp.bfloat16),
        shape(8, w, g, dh, dtype=jnp.bfloat16),
        shape(8, w, g, dh, dtype=jnp.bfloat16),
        shape(8, w, dtype=jnp.bool_))


def test_ssd_scan_compiles(shape):
    cfg = get_config("mamba2-1.3b")
    h, p, n, n_tok = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, 2048
    _assert_mosaic(
        lambda x, dt, a, b, c: ops.ssd_scan(x, dt, a, b, c, cfg.ssm_chunk,
                                            interpret=False),
        shape(1, n_tok, h, p), shape(1, n_tok, h), shape(h),
        shape(1, n_tok, 1, n), shape(1, n_tok, 1, n))
