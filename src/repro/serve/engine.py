"""Continuous-batching serving engine over posit KV caches.

v2 of the serving subsystem: the engine owns one device "lane" per
``ServePolicy`` (shared quantized weights, per-row-length stacked KV
cache, jitted prefill/decode), the ``Scheduler`` owns admission and slot
lifecycle, and the ``TokenLedger`` prices every token (µs + nJ, with the
KV traffic term at the lane's storage width).

Request flow: ``submit()`` → scheduler queue → ``step()`` admits into a
free slot (B=1 right-padded prefill, rows installed into the lane cache),
then one batched decode per lane per step; EOS/budget retires the slot
into a bounded completion queue while the other rows keep decoding.

Sampling keys are derived per request — ``fold_in(fold_in(key(seed),
rid), step)`` — so repeated prompts on one engine don't replay the same
stream (the old engine reused ``jax.random.key(0)`` for every call).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.formats import get_format
from repro.core.policy import QuantPolicy
from repro.core.quant import quantize_params
from repro.models.attention import KVCache
from repro.obs import MetricsRegistry, bind_serving_engine
from repro.stream.engine import bucket_size

from .accounting import (TokenLedger, kv_traffic_bytes, prefill_energy_nj,
                         token_energy_nj)
from .policy import ServePolicy
from .scheduler import Completion, Request, Scheduler


@dataclasses.dataclass
class ServeConfig:
    batch_size: int = 8          # slots per precision lane
    max_prompt: int = 128
    max_new_tokens: int = 32     # per-request default budget
    temperature: float = 0.0     # 0 → greedy
    seed: int = 0                # engine PRNG root (folded with rid, step)
    max_completions: Optional[int] = 256  # drop-oldest completion backlog
    keep_logits: bool = False    # host copy of every emitted token's logits
                                 # row in ``served_logits[rid]``


class _Lane:
    """Device state of one precision lane: model + quantized params +
    stacked per-row caches + per-slot host bookkeeping."""

    def __init__(self, engine: "ServingEngine", sp: ServePolicy):
        cfg = engine.model.cfg
        self.policy = sp
        self.model = type(engine.model)(cfg, engine.model.minfo,
                                        sp.quant_policy())
        self.params = engine._params_for(sp.weights)
        B = engine.cfg.batch_size
        self.capacity = engine.cfg.max_prompt + engine.cfg.max_new_tokens
        self.caches = self.model.init_cache(B, self.capacity, per_row=True)
        self.cur = jnp.zeros((B,), jnp.int32)
        # host-side per-slot metadata (fed to the jitted step as operands)
        self.rids = np.zeros((B,), np.int32)
        self.steps = np.zeros((B,), np.int32)
        self.temps = np.zeros((B,), np.float32)
        self.active = np.zeros((B,), bool)
        self.ctx = np.zeros((B,), np.int64)  # valid cache length per row
        self._prefill = jax.jit(self.model.prefill, static_argnums=(2,))
        self._decode = _make_decode_step(self.model)
        self._seen_ppad: set = set()  # prompt buckets already compiled
        # lane creation builds exactly one decode program per lane
        engine.metrics.counter(
            "jit_programs_total", "compiled programs by site").inc(
                site="serve.decode", lane=sp.lane)


def _make_decode_step(model):
    """One fused device step: decode_step + per-row key derivation +
    temperature/greedy sampling + length freeze of inactive rows.  Returns
    (next tokens, caches, the (B, vocab) f32 logits sampled from)."""
    vocab = model.cfg.vocab

    def fn(params, cur, caches, base_key, rids, steps, temps, active):
        logits, new_caches = model.decode_step(params, cur[:, None], caches)
        lv = logits[:, -1, :vocab].astype(jnp.float32)
        greedy = jnp.argmax(lv, axis=-1).astype(jnp.int32)

        def row_key(rid, step):
            return jax.random.fold_in(jax.random.fold_in(base_key, rid),
                                      step)

        keys = jax.vmap(row_key)(rids, steps)
        safe_t = jnp.where(temps > 0, temps, 1.0)[:, None]
        sampled = jax.vmap(jax.random.categorical)(keys, lv / safe_t)
        nxt = jnp.where(temps > 0, sampled.astype(jnp.int32), greedy)
        # inactive slots decode garbage; freeze their lengths so the
        # next occupant's prefill install starts from a clean row
        new_caches = jax.tree_util.tree_map(
            lambda b, a: KVCache(a.k, a.v,
                                 jnp.where(active, a.length, b.length)),
            caches, new_caches,
            is_leaf=lambda x: isinstance(x, KVCache))
        return nxt, new_caches, lv

    return jax.jit(fn)


class ServingEngine:
    """Multi-lane continuous-batching engine.

    ``policy`` may be a ``ServePolicy`` (serving-native) or a
    ``QuantPolicy`` (legacy contract) — it sets the default lane for
    ``submit``/``generate``; per-request policies open further lanes.
    """

    def __init__(self, model, params, cfg: ServeConfig,
                 policy: Union[ServePolicy, QuantPolicy] = None,
                 metrics=None, tracer=None):
        self.model = model
        self.cfg = cfg
        if policy is None:
            policy = ServePolicy(weights=None, kv=None)
        elif isinstance(policy, QuantPolicy):
            policy = ServePolicy.from_quant_policy(policy)
        self.policy = policy
        self._raw_params = params
        self._quantized: Dict[Optional[str], object] = {}
        self._lanes: Dict[str, _Lane] = {}
        self._base_key = jax.random.key(cfg.seed)
        # observability mirrors the stream engine: a private registry by
        # default, NULL_METRICS to disable, tracer off unless provided
        self.metrics = MetricsRegistry() if metrics is None else metrics
        self.tracer = tracer
        bind_serving_engine(self.metrics, self)
        self._jit_programs = self.metrics.counter(
            "jit_programs_total", "compiled programs by site")
        self._jit_hits = self.metrics.counter(
            "jit_cache_hits_total", "compiled-program cache hits by site")
        self.scheduler = Scheduler(cfg.batch_size, cfg.max_completions,
                                   metrics=self.metrics)
        self.ledger = TokenLedger()
        # rid → logits rows of its emitted tokens (``cfg.keep_logits``)
        self.served_logits: Dict[int, List[np.ndarray]] = {}

    # -- params -----------------------------------------------------------
    def _params_for(self, weights_fmt: Optional[str]):
        """Quantize the raw weights once per storage format; lanes that
        share a weights format share one device copy."""
        if weights_fmt not in self._quantized:
            p = self._raw_params
            if weights_fmt is not None:
                p = quantize_params(p, get_format(weights_fmt),
                                    cast_rest=jnp.bfloat16)
            self._quantized[weights_fmt] = p
        return self._quantized[weights_fmt]

    def _lane(self, sp: ServePolicy) -> _Lane:
        if sp.lane not in self._lanes:
            self._lanes[sp.lane] = _Lane(self, sp)
        return self._lanes[sp.lane]

    # -- request API ------------------------------------------------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None,
               temperature: Optional[float] = None,
               eos_id: Optional[int] = None,
               policy: Optional[ServePolicy] = None) -> int:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) == 0 or len(prompt) > self.cfg.max_prompt:
            raise ValueError(f"prompt length {len(prompt)} outside "
                             f"(0, {self.cfg.max_prompt}]")
        req = Request(
            rid=-1, prompt=prompt,
            max_new_tokens=min(max_new_tokens or self.cfg.max_new_tokens,
                               self.cfg.max_new_tokens),
            temperature=(self.cfg.temperature if temperature is None
                         else temperature),
            eos_id=eos_id, policy=policy or self.policy)
        return self.scheduler.submit(req)

    # -- admission: B=1 ragged prefill, install rows into the lane --------
    def _admit(self, req: Request, slot: int,
               span: Optional[int] = None) -> None:
        """Prefill one admitted request into ``slot``; ``span`` is the id of
        the ``serve/admit`` span its ``serve/prefill`` span belongs to."""
        lane = self._lane(req.policy)
        P = len(req.prompt)
        P_pad = bucket_size(P, self.cfg.max_prompt)
        # prefill retraces once per (lane, prompt bucket): count compiles
        # vs hits so a bucketing regression (every prompt its own shape)
        # shows up as a first-class metric, not a latency mystery
        if P_pad not in lane._seen_ppad:
            lane._seen_ppad.add(P_pad)
            self._jit_programs.inc(site="serve.prefill", lane=req.policy.lane)
        else:
            self._jit_hits.inc(site="serve.prefill", lane=req.policy.lane)
        toks = np.zeros((1, P_pad), np.int32)
        toks[0, :P] = req.prompt  # right-pad; lengths mask the tail
        t0 = time.perf_counter()
        logits, new_caches = lane._prefill(
            lane.params,
            {"tokens": jnp.asarray(toks), "lengths": jnp.asarray([P])},
            lane.capacity)
        # copy the fresh B=1 rows into this slot of the lane's stacked
        # caches (every leaf is (L, B, ...), so one tree_map covers k/v
        # bits and per-row lengths alike)
        lane.caches = jax.tree_util.tree_map(
            lambda big, small: big.at[:, slot].set(small[:, 0]),
            lane.caches, new_caches)
        # first token comes from the prefill logits (step 0 of the key
        # stream for this request)
        lv = logits[0, -1, :self.model.cfg.vocab].astype(jnp.float32)
        if self.cfg.keep_logits:
            self.served_logits[req.rid] = [np.asarray(lv)]
        if req.temperature > 0:
            key = jax.random.fold_in(
                jax.random.fold_in(self._base_key, req.rid), 0)
            tok = int(jax.random.categorical(key, lv / req.temperature))
        else:
            tok = int(jnp.argmax(lv))
        jax.block_until_ready(lane.caches)
        t1 = time.perf_counter()
        if self.tracer is not None:
            self.tracer.complete("serve", "prefill", t0, t1,
                                 track=f"lane:{req.policy.lane}",
                                 args={"rid": req.rid, "P": P,
                                       "P_pad": P_pad, "slot": slot},
                                 parent=span, key=req.rid)
        self.ledger.record_prefill(
            req.policy.lane, P, t1 - t0,
            prefill_energy_nj(self.model.cfg, P, req.policy))
        retired = self.scheduler.on_token(req.policy.lane, slot, tok)
        if retired:
            if self.tracer is not None:
                self.tracer.instant("serve", "retire",
                                    track=f"lane:{req.policy.lane}",
                                    args={"rid": req.rid, "slot": slot},
                                    parent=span, key=req.rid)
            return
        lane.cur = lane.cur.at[slot].set(tok)
        lane.rids[slot] = req.rid
        lane.steps[slot] = 1
        lane.temps[slot] = req.temperature
        lane.active[slot] = True
        lane.ctx[slot] = P

    # -- one engine tick --------------------------------------------------
    def step(self) -> int:
        """Admit what fits, then run one batched decode step per active
        lane.  Returns the number of real tokens emitted."""
        tr = self.tracer
        if tr is not None:
            t_step, sid, decoded = tr.now(), tr.new_id(), {}
        admissions = self.scheduler.take_admissions()
        for req, slot in admissions:
            adm = None
            if tr is not None:
                t_adm, adm = tr.now(), tr.new_id()
            self._admit(req, slot, span=adm)
            if tr is not None:
                tr.complete("serve", "admit", t_adm, tr.now(),
                            track=f"lane:{req.policy.lane}",
                            args={"rid": req.rid, "slot": slot},
                            sid=adm, parent=sid, key=req.rid)
        emitted = 0
        for lane_name in self.scheduler.active_lanes():
            lane = self._lanes[lane_name]
            rows = self.scheduler.active_rows(lane_name)
            lane.active[:] = False
            for i in rows:
                lane.active[i] = True
            t0 = time.perf_counter()
            nxt, lane.caches, lv = lane._decode(
                lane.params, lane.cur, lane.caches, self._base_key,
                jnp.asarray(lane.rids), jnp.asarray(lane.steps),
                jnp.asarray(lane.temps), jnp.asarray(lane.active))
            nxt = jax.block_until_ready(nxt)
            wall = time.perf_counter() - t0
            lane.cur = nxt
            toks = np.asarray(nxt)
            if self.cfg.keep_logits:
                lv = np.asarray(lv)
                for i in rows:
                    self.served_logits[int(lane.rids[i])].append(lv[i])
            energy = 0.0
            kv_read = 0.0
            for i in rows:
                lane.ctx[i] += 1
                energy += token_energy_nj(self.model.cfg, int(lane.ctx[i]),
                                          lane.policy)
                kv_read += kv_traffic_bytes(self.model.cfg,
                                            int(lane.ctx[i]),
                                            lane.policy.kv_bits)[0]
                lane.steps[i] += 1
                if self.scheduler.on_token(lane_name, i, int(toks[i])):
                    lane.active[i] = False
                    if tr is not None:
                        rid = int(lane.rids[i])
                        tr.instant("serve", "retire",
                                   track=f"lane:{lane_name}",
                                   args={"rid": rid, "slot": int(i)},
                                   parent=sid, key=rid)
            emitted += len(rows)
            self.ledger.record_decode(
                lane_name, len(rows), self.cfg.batch_size - len(rows),
                wall, energy, kv_read)
            if tr is not None:
                tr.complete("serve", "decode", t0, t0 + wall,
                            track=f"lane:{lane_name}",
                            args={"rows": len(rows)}, parent=sid)
                tr.complete("serve", "account", t0 + wall, tr.now(),
                            track=f"lane:{lane_name}",
                            args={"rows": len(rows)}, parent=sid)
                decoded[lane_name] = len(rows)
        if tr is not None:
            tr.complete("serve", "step", t_step, tr.now(), track="serve",
                        args={"admitted": len(admissions),
                              "rows": decoded}, sid=sid)
        return emitted

    def run(self) -> List[Completion]:
        """Drive steps until every submitted request has finished."""
        while not self.scheduler.idle:
            self.step()
        return self.scheduler.pop_completions()

    # -- legacy contract --------------------------------------------------
    def generate(self, prompts: List[np.ndarray]) -> List[np.ndarray]:
        """Decode a batch of prompts, outputs in input order (old API)."""
        rids = [self.submit(p) for p in prompts]
        by_rid = {c.rid: c.tokens for c in self.run()}
        return [by_rid[r] for r in rids]
