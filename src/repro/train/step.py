"""Train step builder: loss → grads → (optional posit-compressed cross-pod
all-reduce with error feedback) → AdamW.

Weight quantization during training is QAT-style: master weights stay f32,
the forward sees straight-through posit-rounded values (``fake_quant``) — the
storage benefit accrues at checkpoint/serving time, the accuracy behaviour is
the paper's (posit16 ≈ fp32 forward).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.policy import QuantPolicy
from repro.core.quant import fake_quant
from repro.distributed.collectives import posit_all_reduce
from repro.distributed.sharding import MeshInfo
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.optim.schedule import wsd_schedule


class TrainState(dict):
    """{params, opt: {m,v,step}} plain dict for pytree friendliness."""


def init_train_state(params) -> dict:
    return {"params": params, "opt": adamw_init(params)}


def _apply_weight_quant(params, policy: QuantPolicy):
    fmt = policy.weights
    if fmt is None:
        return params

    def q(x):
        if x.ndim >= 2 and x.dtype in (jnp.float32, jnp.bfloat16):
            return fake_quant(x, fmt)
        return x

    return jax.tree_util.tree_map(q, params)


def make_train_step(model, minfo: MeshInfo, policy: QuantPolicy,
                    opt_cfg: AdamWConfig = AdamWConfig(),
                    microbatches: int = 1, mb_unroll: int = 1):
    """Returns step(state, batch) -> (state, metrics).

    ``microbatches`` > 1 enables gradient accumulation: activations live only
    for one microbatch, cutting peak memory ~microbatches× at the cost of one
    f32 gradient buffer (sharded like the params).
    """

    compress_fmt = policy.fmt("grad_allreduce")
    pod_axis = minfo.pod_axis

    def loss_fn(params, batch):
        p = _apply_weight_quant(params, policy)
        loss, metrics = model.loss(p, batch)
        return loss, metrics

    def single_grads(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def plain_grads(params, batch):
        if microbatches == 1:
            return single_grads(params, batch)
        # gradient accumulation over leading-batch splits
        mbatch = jax.tree_util.tree_map(
            lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                *x.shape[1:]),
            batch)

        def mb_step(acc, mb):
            loss, metrics, grads = single_grads(params, mb)
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return acc, (loss, metrics)

        acc0 = jax.tree_util.tree_map(
            lambda x: jnp.zeros(x.shape, jnp.float32), params)
        gsum, (losses, metricses) = jax.lax.scan(
            mb_step, acc0, mbatch, unroll=mb_unroll)
        grads = jax.tree_util.tree_map(lambda g: g / microbatches, gsum)
        loss = losses.mean()
        metrics = jax.tree_util.tree_map(lambda m: m.mean(), metricses)
        return loss, metrics, grads

    if compress_fmt is not None and pod_axis is not None:
        pod_size = minfo.mesh.shape[pod_axis]
        model._no_logit_wsc = True  # Auto-mesh constraints can't cross the
                                    # Manual pod axis inside shard_map

        def grads_fn(params, batch):
            """Per-pod local grads; posit-compressed cross-pod all-reduce."""

            def pod_local(params, batch):
                loss, metrics, grads = plain_grads(params, batch)
                grads = jax.tree_util.tree_map(
                    lambda g: posit_all_reduce(g, pod_axis, pod_size,
                                               compress_fmt), grads)
                loss = jax.lax.pmean(loss, pod_axis)
                metrics = jax.tree_util.tree_map(
                    lambda m: jax.lax.pmean(m, pod_axis), metrics)
                return loss, metrics, grads

            fn = shard_map(
                pod_local,
                mesh=minfo.mesh,
                in_specs=(P(), P(pod_axis)),
                out_specs=(P(), P(), P()),
                axis_names={pod_axis},
                check_vma=False,
            )
            return fn(params, batch)
    else:
        grads_fn = plain_grads

    def step(state, batch):
        loss, metrics, grads = grads_fn(state["params"], batch)
        lr = wsd_schedule(state["opt"]["step"])
        new_params, new_opt = adamw_update(
            state["params"], grads, state["opt"], lr, opt_cfg)
        metrics = dict(metrics)
        metrics["loss"] = loss
        metrics["lr"] = lr
        return {"params": new_params, "opt": new_opt}, metrics

    return step
