"""Distribution tests on a host-device mesh (these spawn subprocesses with
XLA_FLAGS so the main test process keeps its single CPU device)."""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.parallel.axes import (SINGLE_DEVICE_RULES, TRAIN_RULES,
                                 logical_to_spec)


def test_logical_to_spec_basic():
    spec = logical_to_spec(("batch", "seq", None), TRAIN_RULES, mesh=None)
    assert spec == P(("pod", "data"), "model", None)


def test_logical_to_spec_dedupes_used_axes():
    spec = logical_to_spec(("seq", "heads", None), TRAIN_RULES, mesh=None)
    # both map to "model"; second use must drop it
    assert spec == P("model", None, None)


def test_single_device_rules_all_none():
    spec = logical_to_spec(("batch", "seq", "heads"), SINGLE_DEVICE_RULES)
    assert spec == P(None, None, None)


def _run_subprocess(body: str, devices: int = 8) -> str:
    """Run a snippet under forced host device count; return stdout."""
    prog = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        import sys
        sys.path.insert(0, {os.path.abspath('src')!r})
        import jax, jax.numpy as jnp
        import numpy as np
    """) + textwrap.dedent(body)
    out = subprocess.run([sys.executable, "-c", prog],
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


@pytest.mark.slow
def test_sharded_loss_matches_single_device():
    """The same model + batch must produce the same loss on a 2x4 mesh
    (with SP/TP/fsdp shardings active) as on one device."""
    out = _run_subprocess("""
        from repro.configs.base import get_config
        from repro.configs.inputs import reduced_config
        from repro.models.model import init_params, loss_fn
        from repro.launch.mesh import make_host_mesh
        from repro.parallel.axes import use_sharding, TRAIN_RULES

        cfg = reduced_config(get_config("qwen2.5-3b"))
        params = init_params(jax.random.PRNGKey(0), cfg)
        rng = np.random.default_rng(0)
        toks = jnp.asarray(rng.integers(2, 250, (4, 32)), jnp.int32)
        batch = {"tokens": toks, "labels": toks}
        l0, _ = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
        mesh = make_host_mesh(data=2, model=4)
        with use_sharding(mesh, TRAIN_RULES):
            l1, _ = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
        print("DIFF", abs(float(l0) - float(l1)))
    """)
    diff = float(out.strip().split("DIFF")[1])
    assert diff < 5e-3


@pytest.mark.slow
def test_moe_ep_matches_local_all_mesh_shapes():
    """EP all-to-all MoE == token-local oracle for dup>1, e_loc>1, tp=1."""
    out = _run_subprocess("""
        from repro.configs.base import get_config
        from repro.configs.inputs import reduced_config
        from repro.models import moe as moe_lib
        from repro.models.transformer import moe_ffn
        from repro.parallel.axes import use_sharding, TRAIN_RULES
        from repro.launch.mesh import make_host_mesh

        cfg = reduced_config(get_config("mixtral-8x22b")).replace(
            d_model=64, d_ff=128, n_experts=4, capacity_factor=8.0)
        p = moe_lib.init_moe(jax.random.PRNGKey(0), cfg)
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 64),
                              jnp.float32)
        ref, _ = moe_lib.moe_ffn_local(x.reshape(-1, 64), p, cfg)
        worst = 0.0
        for dn, mn in [(2, 4), (4, 2), (1, 8), (8, 1)]:
            mesh = make_host_mesh(data=dn, model=mn)
            with use_sharding(mesh, TRAIN_RULES):
                out, _ = jax.jit(
                    lambda x, p: moe_ffn(x, p, cfg, True))(x, p)
            worst = max(worst, float(jnp.max(jnp.abs(
                out.reshape(-1, 64) - ref))))
        print("WORST", worst)
    """)
    worst = float(out.strip().split("WORST")[1])
    assert worst < 1e-5


@pytest.mark.slow
def test_gqa_alignment_exact_under_tp():
    """MHA-ize+pad path (H=5 heads, G=1, TP=4): sharded attention must
    equal the unsharded result exactly."""
    out = _run_subprocess("""
        from repro.configs.base import get_config
        from repro.configs.inputs import reduced_config
        from repro.models.attention import blockwise_attention
        from repro.parallel.axes import use_sharding, TRAIN_RULES
        from repro.launch.mesh import make_host_mesh

        cfg = reduced_config(get_config("qwen1.5-0.5b")).replace(
            n_heads=5, n_kv_heads=1, attn_q_chunk=8, attn_kv_chunk=16)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(2, 32, 5, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, 32, 1, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, 32, 1, 16)), jnp.float32)
        base = blockwise_attention(q, k, v, cfg, causal=True)
        mesh = make_host_mesh(data=2, model=4)
        with use_sharding(mesh, TRAIN_RULES):
            sh = jax.jit(lambda q, k, v: blockwise_attention(
                q, k, v, cfg, causal=True))(q, k, v)
        print("DIFF", float(jnp.max(jnp.abs(base - sh))))
    """)
    diff = float(out.strip().split("DIFF")[1])
    assert diff < 1e-5


@pytest.mark.slow
def test_compressed_pmean_under_shard_map():
    """int8 error-feedback mean over a 4-way axis: quantisation error is
    bounded and error feedback carries the residual."""
    out = _run_subprocess("""
        from jax.sharding import Mesh, PartitionSpec as P
        from repro.optim.compress import (compressed_pmean_leaf,
                                          init_error_feedback)
        mesh = Mesh(np.asarray(jax.devices()[:4]), ("pod",))
        g = jax.random.normal(jax.random.PRNGKey(0), (4, 64))
        err = jnp.zeros((4, 64))

        def f(gs, es):
            m, e2 = compressed_pmean_leaf(gs[0], es[0], "pod")
            return m[None], e2[None]

        m, e2 = jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=(P("pod", None), P("pod", None)),
            out_specs=(P("pod", None), P("pod", None)),
            check_vma=False))(g, err)
        true_mean = jnp.mean(g, axis=0)
        got = m[0]
        rel = float(jnp.max(jnp.abs(got - true_mean))
                    / (jnp.max(jnp.abs(true_mean)) + 1e-9))
        # residual is exactly the pre-quantisation value minus the wire value
        print("REL", rel)
    """, devices=4)
    rel = float(out.strip().split("REL")[1])
    assert rel < 0.05            # int8 wire error bound


@pytest.mark.slow
def test_dryrun_smoke_cell_both_meshes():
    """One full dry-run cell on the 16x16 AND 2x16x16 production meshes
    (the multi-pod proof, in miniature run time)."""
    out = _run_subprocess("""
        from repro.configs.base import get_config, SHAPES
        from repro.launch.steps import build_step
        from repro.launch.mesh import make_production_mesh
        from repro.parallel.axes import use_sharding

        for multi in (False, True):
            mesh = make_production_mesh(multi_pod=multi)
            cfg = get_config("qwen1.5-0.5b")
            fn, args, rules = build_step(cfg, SHAPES["train_4k"], mesh)
            with use_sharding(mesh, rules):
                compiled = fn.lower(*args).compile()
            ma = compiled.memory_analysis()
            assert ma.temp_size_in_bytes > 0
            print("MESHOK", mesh.size)
    """, devices=512)
    assert "MESHOK 256" in out and "MESHOK 512" in out
