"""Fault tolerance & elasticity for the training runtime.

At thousand-node scale the framework must survive: node loss (checkpoint +
restart on a smaller mesh), stragglers (step-deadline + skip/requeue), and
grow-back (elastic re-mesh). On real TPU pods the signals come from the
runtime (ICI timeouts, host heartbeats); here the policies are implemented
against simulated signals and exercised in tests — the CONTROL logic is the
deliverable, the detection plumbing is platform glue.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh


from .sharding import MeshInfo


@dataclasses.dataclass
class ElasticConfig:
    model_parallel: int = 16         # fixed TP degree (model must fit)
    min_data_parallel: int = 1
    step_deadline_s: float = 600.0   # straggler: give up on the step
    max_restarts: int = 20


def largest_valid_mesh(n_devices: int, cfg: ElasticConfig
                       ) -> Tuple[int, int]:
    """(data, model) for the biggest usable mesh after losing nodes.

    TP degree is fixed (param shards must fit); the data axis shrinks to the
    largest multiple the surviving devices support. Global batch stays fixed
    — per-device microbatching absorbs the difference (grad-accum).
    """
    tp = cfg.model_parallel
    dp = max(n_devices // tp, cfg.min_data_parallel)
    if n_devices < tp:
        raise RuntimeError(
            f"{n_devices} devices cannot hold a {tp}-way model-parallel "
            "shard set; restore on fewer model shards requires re-sharding "
            "the checkpoint (supported offline via checkpoint.manager)")
    return dp, tp


def remesh(devices: Optional[List] = None,
           cfg: ElasticConfig = ElasticConfig()) -> MeshInfo:
    """Build the largest valid MeshInfo from surviving devices."""
    devices = devices if devices is not None else jax.devices()
    dp, tp = largest_valid_mesh(len(devices), cfg)
    arr = np.asarray(devices[: dp * tp]).reshape(dp, tp)
    mesh = Mesh(arr, ("data", "model"))
    return MeshInfo(mesh, dp_axes=("data",))


class StepWatchdog:
    """Deadline-based straggler mitigation: wraps the blocking step call;
    on deadline the caller skips the step (data is step-indexed, so skipping
    is deterministic and logged) or triggers a restart."""

    def __init__(self, deadline_s: float):
        self.deadline_s = deadline_s
        self.slow_steps: List[int] = []

    def run(self, step_idx: int, fn: Callable, *args):
        t0 = time.monotonic()
        out = fn(*args)
        jax.block_until_ready(out)
        dt = time.monotonic() - t0
        if dt > self.deadline_s:
            self.slow_steps.append(step_idx)
        return out, dt


@dataclasses.dataclass(frozen=True)
class RestartPolicy:
    """Bounded-restart + exponential-backoff policy.

    Shared control logic: the training supervisor (``run_with_restarts``)
    and the ingest worker pool (``repro.ingest.workers``) both respawn a
    failed unit of work at most ``max_restarts`` times, sleeping
    ``delay(attempt)`` before attempt *n* (1-based) — ``backoff_s`` scaled
    by ``backoff_factor`` per prior failure, capped at ``max_backoff_s``.
    """

    max_restarts: int = 3
    backoff_s: float = 0.05
    backoff_factor: float = 2.0
    max_backoff_s: float = 2.0

    def delay(self, attempt: int) -> float:
        """Seconds to back off before restart ``attempt`` (1-based)."""
        return min(self.backoff_s
                   * self.backoff_factor ** max(attempt - 1, 0),
                   self.max_backoff_s)

    def allows(self, restarts_so_far: int) -> bool:
        return restarts_so_far < self.max_restarts


def run_with_restarts(train_once: Callable[[int], int],
                      cfg: ElasticConfig = ElasticConfig(),
                      policy: Optional[RestartPolicy] = None,
                      exceptions: Tuple = (RuntimeError, OSError),
                      sleep: Callable[[float], None] = time.sleep) -> int:
    """Supervisor loop: (re)start training from the latest checkpoint until
    it finishes; each attempt may run on a re-built mesh.

    ``policy`` generalizes the restart budget/backoff (default: the legacy
    behaviour — ``cfg.max_restarts`` attempts, flat 10 ms backoff);
    ``exceptions`` is the retryable set (anything else propagates
    immediately); ``sleep`` is injectable so backoff is testable without
    real waiting."""
    if policy is None:
        policy = RestartPolicy(max_restarts=cfg.max_restarts,
                               backoff_s=0.01, backoff_factor=1.0,
                               max_backoff_s=0.01)
    attempts = 0
    last_step = 0
    while True:
        try:
            return train_once(last_step)
        except exceptions:  # device loss / io failure / worker death
            if not policy.allows(attempts):
                raise RuntimeError(
                    f"exceeded {policy.max_restarts} restarts")
            attempts += 1
            sleep(policy.delay(attempts))
            continue
