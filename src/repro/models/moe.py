"""Mixture-of-Experts with expert parallelism on the model axis.

Design (see DESIGN.md §5): activations are replicated across the model axis
between TP ops, so each expert-owner shard already holds every token — expert
dispatch needs **no all-to-all**: each shard FCFS-selects up to C tokens per
local expert, computes, scatter-adds, and the TP-standard psum combines
expert outputs. Experts are zero-padded to a multiple of the axis size
(granite-moe: 40 → 48); dummy experts receive no tokens.

Implemented as a shard_map island inside the pjit program so capacity
selection stays local and static-shaped.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P


from repro.distributed.sharding import MeshInfo

from .common import Builder, wval


def padded_experts(n_experts: int, tp: int) -> int:
    return (n_experts + tp - 1) // tp * tp


def init_moe(b: Builder, cfg, tp: int) -> dict:
    d, ff, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    Ep = padded_experts(E, tp)
    return {
        "router": b.param("router/w", (d, E), (None, None), scale=0.02),
        "w_gate": b.param("experts/w_gate", (Ep, d, ff), ("model", None, None)),
        "w_up": b.param("experts/w_up", (Ep, d, ff), ("model", None, None)),
        "w_down": b.param("experts/w_down", (Ep, ff, d), ("model", None, None)),
    }


def _capacity(tokens_local: int, cfg) -> int:
    c = int(tokens_local * cfg.top_k / max(cfg.n_experts, 1)
            * cfg.moe_capacity_factor)
    return min(max(8, (c + 7) // 8 * 8), tokens_local)


def moe_ffn(p, x: jax.Array, cfg, minfo: MeshInfo) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) → (out, aux_loss). Runs as a shard_map island."""
    B, S, d = x.shape
    tp_axis = minfo.tp_axis
    dp_axes = tuple(minfo.dp_axes)
    dp, tp = minfo.dp_size, minfo.tp_size
    E, k = cfg.n_experts, cfg.top_k
    Ep = padded_experts(E, tp)
    E_loc = Ep // tp
    assert B % dp == 0, f"MoE batch {B} must divide dp={dp}"
    T_loc = (B // dp) * S
    C = _capacity(T_loc, cfg)

    router_w = wval(p["router"], jnp.float32)
    wg = wval(p["w_gate"])
    wu = wval(p["w_up"])
    wd = wval(p["w_down"])

    def local(xs, rw, wg, wu, wd):
        Bl = xs.shape[0]
        xf = xs.reshape(-1, d)
        T = xf.shape[0]
        logits = (xf.astype(jnp.float32) @ rw)
        gates = jax.nn.softmax(logits, axis=-1)           # (T, E)
        gatev, assign = lax.top_k(gates, k)               # (T, k)
        gatev = gatev / jnp.maximum(gatev.sum(-1, keepdims=True), 1e-9)

        e0 = lax.axis_index(tp_axis) * E_loc
        eids = e0 + jnp.arange(E_loc)
        hit = assign[None, :, :] == eids[:, None, None]   # (E_loc, T, k)
        tok_gate = jnp.sum(hit * gatev[None], axis=-1)    # (E_loc, T)
        routed = hit.any(-1)

        # First-come-first-served capacity: earlier tokens win.
        score = jnp.where(routed, (T - jnp.arange(T)).astype(jnp.float32), 0.0)
        _, idx = lax.top_k(score, C)                      # (E_loc, C)
        valid = jnp.take_along_axis(routed, idx, axis=1)
        w_tok = jnp.take_along_axis(tok_gate, idx, axis=1) * valid

        gath = jnp.take(xf, idx.reshape(-1), axis=0).reshape(E_loc, C, d)
        h = jnp.einsum("ecd,edf->ecf", gath, wg,
                       preferred_element_type=jnp.float32)
        if cfg.ffn_kind == "swiglu":
            u = jnp.einsum("ecd,edf->ecf", gath, wu,
                           preferred_element_type=jnp.float32)
            h = jax.nn.silu(h) * u
        else:
            h = jax.nn.gelu(h)
        y = jnp.einsum("ecf,efd->ecd", h.astype(xs.dtype), wd,
                       preferred_element_type=jnp.float32)
        y = y * w_tok[..., None]

        out = jnp.zeros((T, d), jnp.float32)
        out = out.at[idx.reshape(-1)].add(y.reshape(-1, d))
        out = lax.psum(out, tp_axis)

        # Load-balance aux loss (Switch-style): E * Σ_e f_e · P_e.
        f_e = jnp.mean(
            (assign[..., None] == jnp.arange(E)).any(1).astype(jnp.float32), 0)
        p_e = jnp.mean(gates, axis=0)
        aux = E * jnp.sum(f_e * p_e)
        aux = lax.pmean(aux, dp_axes)

        return out.astype(xs.dtype).reshape(Bl, S, d), aux

    fn = shard_map(
        local,
        mesh=minfo.mesh,
        in_specs=(P(dp_axes, None, None), P(None, None),
                  P(tp_axis, None, None), P(tp_axis, None, None),
                  P(tp_axis, None, None)),
        out_specs=(P(dp_axes, None, None), P()),
        check_vma=False,
    )
    return fn(x, router_w, wg, wu, wd)
