"""Path-based sharding rules for parameter and cache pytrees.

Rules are matched on leaf path names (the Builder naming conventions are the
contract). Dims that don't divide the mesh axis degrade to replication —
recorded by the dry-run, not silently ignored.
"""
from __future__ import annotations

from typing import Any, List, Tuple

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.quant import PositTensor

from .sharding import MeshInfo

# column-parallel (shard output features = last dim)
COL_PAR = {"wq", "wk", "wv", "w_gate", "w_up", "in_proj", "w_z", "w_x",
           "w_h", "w_i", "w_f"}
# row-parallel (shard input features = dim -2 of the weight)
ROW_PAR = {"wo", "w_down", "out_proj", "w_out"}


def _path_names(path) -> List[str]:
    names = []
    for e in path:
        if hasattr(e, "key"):
            names.append(str(e.key))
        elif hasattr(e, "name"):
            names.append(str(e.name))
        else:
            names.append(str(e))
    return names


def _leaf_spec(names: List[str], shape: Tuple[int, ...], minfo: MeshInfo) -> P:
    tp, tpax = minfo.tp_size, minfo.tp_axis
    nd = len(shape)

    def at(dim: int) -> P:
        if dim < 0:
            dim += nd
        if dim < 0 or dim >= nd or shape[dim] % tp != 0:
            return P()
        spec = [None] * nd
        spec[dim] = tpax
        return P(*spec)

    leaf = names[-1] if names else ""
    base = leaf if leaf not in ("w", "b") else (names[-2] if len(names) >= 2 else leaf)

    if base == "table":
        return at(nd - 2)  # (vocab, d) [padded /128 → divisible]
    if "moe" in names and base in ("w_gate", "w_up", "w_down"):
        return at(nd - 3)  # experts dim (padded to tp multiple)
    if base in ROW_PAR and leaf == "w":
        return at(nd - 2)
    if base in COL_PAR:
        return at(nd - 1)  # w and its bias both shard the feature dim
    return P()


def params_shardings(minfo: MeshInfo, params_like) -> Any:
    """NamedSharding tree matching ``params_like`` (SDS or arrays).

    PositTensor nodes are treated as leaves and receive the sharding of their
    bit tensor (tree-prefix semantics cover the scale if present).
    """

    def visit(path, leaf):
        names = _path_names(path)
        if isinstance(leaf, PositTensor):
            # scale-less PositTensors flatten to a single child, so a plain
            # NamedSharding works as a tree prefix (scaled tensors don't
            # compose with jit in_shardings — dry-run trees must be unscaled)
            assert leaf.scale is None, f"scaled PositTensor at {names}"
            return NamedSharding(
                minfo.mesh, _leaf_spec(names, leaf.bits.shape, minfo))
        return NamedSharding(minfo.mesh, _leaf_spec(names, leaf.shape, minfo))

    return jax.tree_util.tree_map_with_path(
        visit, params_like, is_leaf=lambda x: isinstance(x, PositTensor))


def _first_fit_cache_spec(shape, minfo: MeshInfo) -> P:
    """Caches: dp on the first divisible dim (batch; for batch=1 cells it
    falls through to the kv-head, then the sequence dim of the head-major
    KV layout); tp on the LAST divisible dim (head_dim / state features).

    Perf note (§Perf iteration 1): tp must NOT land on the cache's sequence
    dim — decode writes one token at a dynamic index, and a dynamic-update-
    slice across shard boundaries makes XLA all-gather the whole cache
    (observed: +51 GB collectives/step on qwen2.5-14b decode_32k before
    this rule; see EXPERIMENTS.md §Perf).
    """
    dp, tp = minfo.dp_size, minfo.tp_size
    nd = len(shape)
    spec: List[Any] = [None] * nd
    dp_spec = tuple(minfo.dp_axes) if len(minfo.dp_axes) > 1 else minfo.dp_axes[0]
    dp_dim = None
    for d in range(nd):
        if shape[d] % dp == 0 and shape[d] > 1:
            spec[d] = dp_spec
            dp_dim = d
            break
    for d in range(nd - 1, -1, -1):
        if d != dp_dim and shape[d] % tp == 0 and shape[d] > 1:
            spec[d] = minfo.tp_axis
            break
    return P(*spec)


def cache_shardings(minfo: MeshInfo, cache_like) -> Any:
    def one(shape):
        if len(shape) == 0:
            return NamedSharding(minfo.mesh, P())
        return NamedSharding(minfo.mesh, _first_fit_cache_spec(shape, minfo))

    def visit(leaf):
        if isinstance(leaf, PositTensor):
            assert leaf.scale is None, "dry-run cache trees must be unscaled"
            return one(leaf.bits.shape)
        return one(leaf.shape)

    return jax.tree_util.tree_map(
        visit, cache_like, is_leaf=lambda x: isinstance(x, PositTensor))


def batch_shardings(minfo: MeshInfo, batch_like) -> Any:
    dp = minfo.dp_size
    dp_spec = tuple(minfo.dp_axes) if len(minfo.dp_axes) > 1 else minfo.dp_axes[0]

    def visit(leaf):
        if leaf.ndim >= 1 and leaf.shape[0] % dp == 0 and leaf.shape[0] > 1:
            return NamedSharding(minfo.mesh, P(*([dp_spec] + [None] * (leaf.ndim - 1))))
        return NamedSharding(minfo.mesh, P())

    return jax.tree_util.tree_map(visit, batch_like)


def zero1_shardings(minfo: MeshInfo, params_like) -> Any:
    """Optimizer-state shardings: params sharding + the data axis on the
    first still-replicated divisible dim (ZeRO-1). Cuts m/v memory by dp×.
    """
    base = params_shardings(minfo, params_like)
    dp = minfo.dp_size
    dp_axes = tuple(minfo.dp_axes) if len(minfo.dp_axes) > 1 else minfo.dp_axes[0]

    def visit(leaf_like, sh):
        shape = leaf_like.bits.shape if isinstance(leaf_like, PositTensor) \
            else leaf_like.shape
        spec = list(sh.spec) + [None] * (len(shape) - len(sh.spec))
        for d in range(len(shape)):
            if spec[d] is None and shape[d] % dp == 0 and shape[d] > 1:
                spec[d] = dp_axes
                return NamedSharding(minfo.mesh, P(*spec))
        return sh

    return jax.tree_util.tree_map(
        visit, params_like, base,
        is_leaf=lambda x: isinstance(x, (PositTensor, NamedSharding)))
