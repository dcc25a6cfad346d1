"""Production meshes + sharding rules for every (arch × shape) cell.

Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
Multi-pod:  (pod=2, data=16, model=16) = 512 chips; 'pod' is the outer
data-parallel axis (DCN-connected), so batch shards over ('pod','data').

Importing this module never touches jax device state — meshes are built by
FUNCTIONS only (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

from typing import Any

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import params as P_
from repro.models.config import ModelConfig


def _axis_kw(n_axes: int) -> dict:
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n_axes}


V5E = {
    "peak_flops_bf16": 197e12,  # per chip
    "hbm_bw": 819e9,  # bytes/s per chip
    "ici_bw": 50e9,  # bytes/s per link
    "hbm_bytes": 16e9,
}


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = int(np.prod(shape))
    devices = jax.devices()
    if len(devices) == n:
        return jax.make_mesh(shape, axes, **_axis_kw(len(axes)))
    # subset mesh (e.g. single-pod 256 of 512 host devices, or CPU tests)
    assert len(devices) >= n, f"need {n} devices, have {len(devices)}"
    dev_array = np.asarray(devices[:n]).reshape(shape)
    return Mesh(dev_array, axes, **_axis_kw(len(axes)))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]) -> Mesh:
    n = int(np.prod(shape))
    dev_array = np.asarray(jax.devices()[:n]).reshape(shape)
    return Mesh(dev_array, axes, **_axis_kw(len(axes)))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def rules_for(mesh: Mesh, fsdp: bool = True) -> dict[str, Any]:
    """Logical-axis → mesh-axis rules (params)."""
    rules = dict(P_.DEFAULT_RULES)
    rules["embed"] = batch_axes(mesh) if fsdp else None
    return rules


def param_shardings(specs, mesh: Mesh, fsdp: bool = True):
    """NamedShardings for a spec tree with divisibility fallback."""
    pspecs = P_.validate_divisibility(specs, mesh, rules_for(mesh, fsdp))
    return jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs)


def data_sharding(mesh: Mesh):
    return NamedSharding(mesh, P(batch_axes(mesh)))


def _dim_ok(mesh: Mesh, axes, dim: int) -> bool:
    size = int(np.prod([mesh.shape[a] for a in (axes if isinstance(axes, tuple) else (axes,))]))
    return dim % size == 0


def _greedy_pspec(shape: tuple[int, ...], prefs: list[tuple[int, list]], mesh: Mesh) -> P:
    """Assign mesh axes to dims greedily.

    prefs: [(dim, [axis-or-axistuple candidates in priority order]), ...].
    Each mesh axis is used at most once; a candidate applies only if the dim
    is divisible by the candidate's total size.
    """
    used: set[str] = set()
    out: list[Any] = [None] * len(shape)
    for dim, candidates in prefs:
        for cand in candidates:
            axes = cand if isinstance(cand, tuple) else (cand,)
            if not axes or any(a in used or a not in mesh.axis_names for a in axes):
                continue
            size = int(np.prod([mesh.shape[a] for a in axes]))
            if size > 1 and shape[dim] % size == 0:
                out[dim] = cand
                used.update(axes)
                break
    return P(*out)


def cache_pspec_for(path_key: str, shape: tuple[int, ...], mesh: Mesh) -> P:
    """KV-cache / SSM-state sharding by leaf name (leading dim = scan layers,
    replicated).

    Preferences encode the serving layouts:
      * batch over ('pod','data') when divisible (decode_32k);
      * KV heads over 'model' when divisible, else cache SEQUENCE over
        'model' (GQA with few KV heads: qwen3/danube/jamba);
      * batch=1 long-context (long_500k): sequence shards over ALL axes —
        sequence-parallel decode, GSPMD turns the attention reduction into
        psums over the sharded length.
    """
    ba = batch_axes(mesh)
    all_ax = tuple(mesh.axis_names)
    if path_key in ("k", "v"):  # [L, B, slots, kv, hd]
        return _greedy_pspec(
            shape,
            [(1, [ba]), (3, ["model"]), (2, [all_ax, ("data", "model"), "model", ba])],
            mesh,
        )
    if path_key in ("ckv", "kr"):  # [L, B, S, r]
        return _greedy_pspec(
            shape, [(1, [ba]), (2, [all_ax, ("data", "model"), "model", ba])], mesh
        )
    if path_key == "h":  # [L, B, nh, ds, hd]
        return _greedy_pspec(shape, [(1, [ba]), (2, ["model"])], mesh)
    if path_key == "conv":  # [L, B, K-1, conv_dim]
        return _greedy_pspec(shape, [(1, [ba]), (3, ["model"])], mesh)
    if path_key == "pos":  # [L, B]
        return _greedy_pspec(shape, [(1, [ba])], mesh)
    if path_key == "slot_pos":  # [L, B, slots]
        return _greedy_pspec(
            shape, [(1, [ba]), (2, [all_ax, ("data", "model"), "model", ba])], mesh
        )
    return P(*([None] * len(shape)))


def cache_shardings(cache_sds, mesh: Mesh):
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_sds)
    out = []
    for path, leaf in flat:
        key = str(path[-1].key) if hasattr(path[-1], "key") else ""
        out.append(NamedSharding(mesh, cache_pspec_for(key, leaf.shape, mesh)))
    return jax.tree_util.tree_unflatten(treedef, out)
