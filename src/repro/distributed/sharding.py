"""Logical-axis sharding rules for the production meshes.

Single pod:  (data=16, model=16)           — 256 chips
Multi-pod:   (pod=2, data=16, model=16)    — 512 chips

Logical axes used by the model zoo:
  batch   → (pod, data)     activations' leading dim
  vocab   → model            embedding/unembedding tables (padded to /128)
  heads   → model            attention heads (falls back to replicate if the
                             head count does not divide the axis — e.g.
                             granite-moe's 24 heads on a 16-way axis)
  ff      → model            FFN hidden dim
  experts → model            MoE expert dim (expert parallelism)
  dmodel  → None             kept replicated (activations between TP ops)

JAX's NamedSharding requires exact divisibility, so ``logical_spec`` checks
each dim and degrades to replication rather than failing — the dry-run output
records where that happened.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P



@dataclasses.dataclass(frozen=True)
class MeshInfo:
    """Mesh + the role each axis plays."""

    mesh: Mesh
    dp_axes: Tuple[str, ...]  # batch data-parallel axes, e.g. ("pod", "data")
    tp_axis: str = "model"

    @property
    def dp_size(self) -> int:
        size = 1
        for a in self.dp_axes:
            size *= self.mesh.shape[a]
        return size

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]

    @property
    def pod_axis(self) -> Optional[str]:
        return "pod" if "pod" in self.mesh.shape else None

    def axis_size(self, name) -> int:
        if isinstance(name, (tuple, list)):
            s = 1
            for a in name:
                s *= self.mesh.shape[a]
            return s
        return self.mesh.shape[name]


def _axis_fits(dim_size: int, axis_size: int) -> bool:
    return dim_size % axis_size == 0


def logical_spec(minfo: MeshInfo, dims: Sequence[Tuple[int, Optional[str]]]) -> P:
    """Build a PartitionSpec from (dim_size, logical_axis) pairs.

    logical_axis ∈ {"batch", "model", None}; degrades to None when the size
    does not divide the mesh axis.
    """
    spec = []
    for size, logical in dims:
        if logical is None:
            spec.append(None)
        elif logical == "batch":
            if _axis_fits(size, minfo.dp_size):
                spec.append(tuple(minfo.dp_axes) if len(minfo.dp_axes) > 1
                            else minfo.dp_axes[0])
            else:
                spec.append(None)
        elif logical == "model":
            if _axis_fits(size, minfo.tp_size):
                spec.append(minfo.tp_axis)
            else:
                spec.append(None)
        else:
            raise ValueError(f"unknown logical axis {logical!r}")
    return P(*spec)


def shard_leaf(minfo: MeshInfo, dims) -> NamedSharding:
    return NamedSharding(minfo.mesh, logical_spec(minfo, dims))


def replicated(minfo: MeshInfo) -> NamedSharding:
    return NamedSharding(minfo.mesh, P())


# ---------------------------------------------------------------------------
# Fleet dispatch sharding: patient-batched window functions over the data axis
# ---------------------------------------------------------------------------

def fleet_pad(n: int, n_shards: int) -> int:
    """Smallest multiple of ``n_shards`` ≥ ``n`` — the batch size a sharded
    dispatch pads to so every device gets an equal slab.  Padding rows are
    zeros and, because the window functions are row-independent, never
    affect real rows (the same contract the single-device bucket padding
    relies on)."""
    if n_shards < 1:
        raise ValueError(f"n_shards must be ≥ 1, got {n_shards}")
    return -(-int(n) // int(n_shards)) * int(n_shards)


@functools.lru_cache(maxsize=None)
def _fleet_batch_fn_cached(fn, minfo: MeshInfo):
    from repro.distributed.collectives import ledger_psum

    axes = tuple(minfo.dp_axes)
    spec = P(axes if len(axes) > 1 else axes[0])

    def local(arrays, mask):
        outs = fn(arrays)
        # device-local ledger row: [real windows, padding rows] — reduced
        # through the collectives psum path so the host-side ledger records
        # the fleet total, not one shard's view
        row = jnp.stack([jnp.sum(mask), jnp.sum(1 - mask)])
        return outs, ledger_psum(row, axes)

    sm = shard_map(local, mesh=minfo.mesh, in_specs=(spec, spec),
                   out_specs=(spec, P()), check_vma=False)
    return jax.jit(sm)


def make_fleet_batch_fn(fn, minfo: MeshInfo):
    """Wrap a row-independent batched window function for multi-device
    dispatch: inputs (a dict of ``(B, channels, n)`` arrays plus a ``(B,)``
    int32 real-row mask) are sharded on the leading patient/window dim over
    the mesh's data axis, each device runs the identical per-row graph on
    its slab, and the device-local ledger row ``[real, padded]`` is reduced
    through ``collectives.ledger_psum``.

    ``B`` must be a multiple of ``minfo.dp_size`` (use ``fleet_pad``).  Any
    non-data mesh axes see the inputs replicated — the spec only names the
    data axes, so ``logical_spec``-style replication fallback applies to
    everything else.

    Bit-identity contract (see ``distributed/README.md``): per-row graphs
    are identical to the single-device path — sharding splits only the
    leading dim, every in-row shape is unchanged — so outputs match the
    unsharded dispatch bitwise.  Cached per (fn, mesh): engines sharing one
    pipeline share the compiled sharded program.
    """
    return _fleet_batch_fn_cached(fn, minfo)
