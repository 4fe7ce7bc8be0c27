"""Logical-axis sharding system for the (pod, data, model) production mesh.

Model code never names mesh axes directly: tensors are annotated with
*logical* axis names (``constrain(x, "batch", "seq", None)``) and a rule set
maps logical names to mesh axes. Rule sets differ per execution phase:

  TRAIN_RULES         batch over (pod, data); Megatron-style sequence
                      parallelism between blocks (seq over model); heads /
                      ffn / vocab over model; fsdp (param embed dim) over data
  SSM_PREFILL_RULES   like TRAIN but seq unsharded (SSD chunk scan carries
                      sequential state along seq; sharding it would force
                      GSPMD to serialise)
  DECODE_RULES        batch over (pod, data); no SP (seq axis = cache
                      positions, sharded over model only for attention KV)
  SINGLE_DEVICE_RULES everything replicated (smoke tests, CPU)

Without an active mesh (``use_sharding`` context) every annotation is an
identity, so the same model code runs on one CPU device in tests.
"""

from __future__ import annotations

import contextlib
import functools
import threading
from typing import Mapping, Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

LogicalRules = Mapping[str, Optional[Sequence[str] | str]]

# fsdp: weights' embed dim sharded over data (ZeRO-3 style gather at use)
TRAIN_RULES: LogicalRules = {
    "batch": ("pod", "data"),
    "seq": "model",            # sequence parallelism between blocks
    "seq_noshard": None,
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "embed": None,             # activation embed dim
    "embed_p": "data",         # parameter embed dim (fsdp)
    "ffn": "model",
    "vocab": "model",
    "experts": None,
    "cap": None,
    "state": None,
    "layers": None,
    "cache_seq": "model",
    "apps": None,
}

SSM_PREFILL_RULES: LogicalRules = dict(TRAIN_RULES, seq=None)

DECODE_RULES: LogicalRules = dict(TRAIN_RULES, seq=None)

SINGLE_DEVICE_RULES: LogicalRules = {k: None for k in TRAIN_RULES}


class _Ctx(threading.local):
    mesh: Optional[Mesh] = None
    rules: LogicalRules = SINGLE_DEVICE_RULES


_CTX = _Ctx()


@contextlib.contextmanager
def use_sharding(mesh: Optional[Mesh], rules: LogicalRules):
    """Activate a mesh + logical rule set for model code built inside."""
    prev = (_CTX.mesh, _CTX.rules)
    _CTX.mesh, _CTX.rules = mesh, rules
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def current_rules() -> LogicalRules:
    return _CTX.rules


def axis_size(name: str) -> int:
    """Size of a mesh axis under the active mesh (1 if none)."""
    mesh = _CTX.mesh
    if mesh is None or name not in mesh.shape:
        return 1
    return mesh.shape[name]


def logical_to_spec(logical_axes: Sequence[Optional[str]],
                    rules: Optional[LogicalRules] = None,
                    mesh: Optional[Mesh] = None) -> P:
    """Translate logical axis names to a PartitionSpec under ``rules``.

    Mesh axes absent from ``mesh`` (or the active mesh) are dropped — the
    same rule set serves the 2x16x16 multi-pod mesh (with its "pod" axis)
    and the 16x16 single-pod mesh.
    """
    rules = rules if rules is not None else _CTX.rules
    mesh = mesh if mesh is not None else _CTX.mesh
    spec = []
    used: set[str] = set()
    for name in logical_axes:
        if name is None:
            spec.append(None)
            continue
        axes = rules.get(name)
        if axes is None:
            spec.append(None)
            continue
        if isinstance(axes, str):
            axes = (axes,)
        axes = tuple(a for a in axes if a not in used
                     and (mesh is None or a in mesh.shape))
        used.update(axes)
        spec.append(axes if len(axes) > 1 else (axes[0] if axes else None))
    return P(*spec)


def sanitized_spec(shape: Sequence[int],
                   logical_axes: Sequence[Optional[str]],
                   rules: Optional[LogicalRules] = None,
                   mesh: Optional[Mesh] = None) -> P:
    """`logical_to_spec` with divisibility enforcement: mesh axes that do
    not evenly divide the corresponding dim are dropped (required for jit
    argument shardings and shard_map in_specs)."""
    mesh = mesh if mesh is not None else _CTX.mesh
    spec = logical_to_spec(logical_axes, rules, mesh)
    if mesh is None:
        return spec
    out = []
    for i, axes in enumerate(spec):
        if axes is None:
            out.append(None)
            continue
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        keep = []
        size = shape[i] if i < len(shape) else 1
        for a in axes_t:
            if a not in mesh.shape:
                continue
            n = mesh.shape[a]
            if size % n == 0:
                keep.append(a)
                size //= n
        out.append(tuple(keep) if len(keep) > 1
                   else (keep[0] if keep else None))
    return P(*out)


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """`with_sharding_constraint` by logical names; identity with no mesh."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = logical_to_spec(logical_axes)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, spec))


def named_sharding(*logical_axes: Optional[str],
                   mesh: Optional[Mesh] = None,
                   rules: Optional[LogicalRules] = None) -> NamedSharding:
    mesh = mesh if mesh is not None else _CTX.mesh
    if mesh is None:
        raise ValueError("no active mesh")
    return NamedSharding(mesh, logical_to_spec(logical_axes, rules))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def psum_id(x, axis_name: str):
    """`jax.lax.psum` whose backward pass is the identity.

    Inside a `shard_map` body (``check_vma=False``), differentiating a
    raw ``psum`` applies ``psum`` to the cotangent too, multiplying the
    gradient by the shard count — the cotangent of a fleet aggregate is
    already replicated (every shard forms the same downstream loss from
    it), so summing it across shards over-counts by exactly ``n_sh``.
    With the identity backward, the per-shard gradient of a loss built
    on `psum_id`-reduced aggregates equals the single program's per-row
    gradient exactly.

    Contract: only valid when every shard consumes the reduced value
    through the same expression (replicated cotangent) — true for the
    coupled fleet aggregates in `repro.tune.objective`, not for
    arbitrary per-shard weightings of the reduced value.
    """
    return jax.lax.psum(x, axis_name)


def _psum_id_fwd(x, axis_name):
    return jax.lax.psum(x, axis_name), None


def _psum_id_bwd(axis_name, _, ct):
    return (ct,)


psum_id.defvjp(_psum_id_fwd, _psum_id_bwd)


def row_mesh(n: int, axis: str = "rows") -> Mesh:
    """1-D mesh over the first ``n`` local devices.

    The batch-sharding mesh of data-parallel scenario work — the
    tuner's `shard_map`-over-B hot loop (`repro.tune.optimizer`) splits
    independent grid rows across it. Orthogonal to the logical-axis
    model meshes above: rows are embarrassingly parallel, so no rule
    set is involved.
    """
    import numpy as np
    devices = jax.devices()
    if n > len(devices):
        raise ValueError(f"row_mesh({n}) but only {len(devices)} devices")
    return Mesh(np.asarray(devices[:n]), (axis,))
