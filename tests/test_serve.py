"""Serving subsystem: scheduler lifecycle, ragged-prefill parity,
per-request sampling keys, nJ/token accounting, and the BENCH_serve.json
schema pin."""
import dataclasses
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS, reduced
from repro.launch.mesh import make_debug_mesh_info
from repro.models import build_model
from repro.serve import (AGGRESSIVE_SERVE, Completion, Request, ServeConfig,
                         ServePolicy, ServingEngine, Scheduler)
from repro.serve.accounting import (kv_traffic_bytes, prefill_energy_nj,
                                    token_energy_nj)


def _req(rid=-1, plen=4, max_new=3, eos=None, policy=AGGRESSIVE_SERVE):
    return Request(rid=rid, prompt=np.arange(1, plen + 1, dtype=np.int32),
                   max_new_tokens=max_new, eos_id=eos, policy=policy)


# ---------------------------------------------------------------------------
# Scheduler: pure bookkeeping (no device code)
# ---------------------------------------------------------------------------
def test_scheduler_admission_and_slot_reuse():
    s = Scheduler(batch_size=2)
    for _ in range(5):
        s.submit(_req(max_new=2))
    adm = s.take_admissions()
    assert [slot for _, slot in adm] == [0, 1]      # FIFO into free slots
    assert len(s.waiting) == 3 and s.take_admissions() == []
    lane = adm[0][0].policy.lane
    # finish slot 1 first: its slot must be reused by the NEXT request
    # while slot 0 keeps decoding (continuous batching, not batch barriers)
    s.on_token(lane, 1, 7)
    assert s.on_token(lane, 1, 8)                   # budget of 2 → retired
    adm2 = s.take_admissions()
    assert len(adm2) == 1 and adm2[0][1] == 1
    assert adm2[0][0].rid == 2                      # FIFO order preserved
    assert s.active_rows(lane) == [0, 1]


def test_scheduler_eos_vs_length_and_idle():
    s = Scheduler(batch_size=1)
    r_eos = s.submit(_req(max_new=5, eos=99))
    (req, slot), = s.take_admissions()
    lane = req.policy.lane
    s.on_token(lane, slot, 3)
    assert s.on_token(lane, slot, 99)               # EOS retires early
    r_len = s.submit(_req(max_new=1))
    (req, slot), = s.take_admissions()
    assert s.on_token(lane, slot, 5)
    comps = {c.rid: c for c in s.pop_completions()}
    assert comps[r_eos].finish_reason == "eos"
    assert list(comps[r_eos].tokens) == [3, 99]     # EOS token included
    assert comps[r_len].finish_reason == "length"
    assert s.idle and s.pop_completions() == []


def test_scheduler_completion_queue_bounded_drop_oldest():
    s = Scheduler(batch_size=1, max_completions=2)
    rids = []
    for _ in range(4):
        rids.append(s.submit(_req(max_new=1)))
        (req, slot), = s.take_admissions()
        import contextlib
        ctx = (pytest.warns(RuntimeWarning) if len(rids) > 2
               else contextlib.nullcontext())
        with ctx:
            s.on_token(req.policy.lane, slot, 1)
    got = [c.rid for c in s.pop_completions()]
    assert got == rids[2:]                          # oldest two dropped
    assert s.dropped == 2


def test_scheduler_lanes_are_independent():
    s = Scheduler(batch_size=1)
    a = ServePolicy(weights="posit16", kv="posit8")
    b = ServePolicy(weights="posit16", kv="posit16")
    s.submit(_req(policy=a))
    s.submit(_req(policy=b))
    adm = s.take_admissions()
    assert len(adm) == 2                            # one slot PER LANE
    assert {req.policy.lane for req, _ in adm} == {a.lane, b.lane}
    assert sorted(s.active_lanes()) == sorted([a.lane, b.lane])


# ---------------------------------------------------------------------------
# Accounting: the KV traffic term prices the STORAGE width
# ---------------------------------------------------------------------------
def test_token_energy_scales_with_kv_width_and_context():
    cfg = reduced(CONFIGS["qwen3-8b"])
    p8 = ServePolicy(weights="posit16", kv="posit8")
    p16 = ServePolicy(weights="posit16", kv="posit16")
    r8, w8 = kv_traffic_bytes(cfg, 100, 8)
    r16, w16 = kv_traffic_bytes(cfg, 100, 16)
    assert r8 * 2 == r16 and w8 * 2 == w16          # half width, half bytes
    e8, e16 = token_energy_nj(cfg, 100, p8), token_energy_nj(cfg, 100, p16)
    assert e8 < e16                                 # narrower cache, less nJ
    # same policy, longer context → strictly more energy (attention + KV)
    assert token_energy_nj(cfg, 200, p8) > e8
    assert prefill_energy_nj(cfg, 8, p8) > 0


# ---------------------------------------------------------------------------
# Engine (reduced LM): ragged prefill parity, keys, continuous batching
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served_model():
    cfg = reduced(CONFIGS["qwen3-8b"])
    minfo = make_debug_mesh_info()
    with minfo.mesh:
        model = build_model(cfg, minfo)
        params = model.init(jax.random.key(0))
    return cfg, minfo, model, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab, size=n).astype(np.int32)
            for n in lens]


def test_ragged_prefill_logits_match_unbatched(served_model):
    """The left-pad regression: padded-batch prefill logits must equal each
    prompt's UNBATCHED prefill logits (pad rows masked, last-real-token
    gather), not logits over a shifted window."""
    cfg, minfo, model, params = served_model
    prompts = _prompts(cfg, [5, 3, 9])
    S = max(len(p) for p in prompts)
    toks = np.zeros((len(prompts), S), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    lengths = np.asarray([len(p) for p in prompts])
    with minfo.mesh:
        batched, caches = model.prefill(
            params, {"tokens": jnp.asarray(toks),
                     "lengths": jnp.asarray(lengths)}, S)
        for i, p in enumerate(prompts):
            solo, _ = model.prefill(params, {"tokens": jnp.asarray(p[None])},
                                    len(p))
            np.testing.assert_allclose(
                np.asarray(batched[i, 0], np.float32),
                np.asarray(solo[0, -1], np.float32), rtol=2e-2, atol=2e-2)
        # caches carry each row's true length (continuous-batching layout;
        # length is (L, B) on the layer-stacked cache)
        np.testing.assert_array_equal(np.asarray(caches.length),
                                      np.tile(lengths, (cfg.n_layers, 1)))


def test_engine_continuous_batching_and_lanes(served_model):
    """5 requests through 2 slots, one on a second precision lane: all
    complete, budgets honoured, ledger sees both lanes."""
    cfg, minfo, model, params = served_model
    with minfo.mesh:
        eng = ServingEngine(model, params,
                            ServeConfig(batch_size=2, max_prompt=16,
                                        max_new_tokens=4, seed=3),
                            AGGRESSIVE_SERVE)
        prompts = _prompts(cfg, [5, 3, 9, 4, 7], seed=1)
        rids = [eng.submit(p) for p in prompts[:4]]
        rids.append(eng.submit(
            prompts[4], max_new_tokens=2,
            policy=ServePolicy(weights="posit16", kv="posit16")))
        comps = {c.rid: c for c in eng.run()}
    assert sorted(comps) == sorted(rids)
    assert all(len(comps[r].tokens) == 4 for r in rids[:4])
    assert len(comps[rids[4]].tokens) == 2
    assert all(c.finish_reason == "length" for c in comps.values())
    summary = eng.ledger.summary()
    assert {"w=posit16/kv=posit8/act=-", "w=posit16/kv=posit16/act=-",
            "fleet"} <= set(summary)
    fleet = summary["fleet"]
    # each request's FIRST token is sampled from the prefill logits, so
    # decode steps account for total − requests tokens
    assert fleet["decode_tokens"] == (4 * 4 + 2) - 5
    assert fleet["requests"] == 5 and fleet["nj_per_token"] > 0


def test_engine_per_request_keys_do_not_replay(served_model):
    """The old engine reused jax.random.key(0) for every generate() call:
    identical prompts always produced identical samples.  Keys are now
    fold_in(engine_seed, rid, step): same prompt twice on ONE engine gives
    distinct streams, while a fresh engine with the same seed reproduces
    the same rid→stream mapping (determinism is keyed, not lost)."""
    cfg, minfo, model, params = served_model

    def run_twice(seed):
        with minfo.mesh:
            eng = ServingEngine(model, params,
                                ServeConfig(batch_size=2, max_prompt=8,
                                            max_new_tokens=4, seed=seed))
            p = _prompts(cfg, [6], seed=2)[0]
            r1 = eng.submit(p, temperature=1.0)
            r2 = eng.submit(p, temperature=1.0)
            out = {c.rid: c.tokens for c in eng.run()}
        return out[r1], out[r2]

    a1, a2 = run_twice(seed=11)
    assert not np.array_equal(a1, a2)       # rid folds into the key
    b1, b2 = run_twice(seed=11)
    np.testing.assert_array_equal(a1, b1)   # same seed → reproducible
    np.testing.assert_array_equal(a2, b2)


def test_engine_keeps_the_logits_it_sampled_from(served_model):
    """keep_logits: one logits row per emitted token, from the programs
    that served it (the B=1 prefill, then the batched decode), and every
    greedy token is the argmax of its own row."""
    cfg, minfo, model, params = served_model
    with minfo.mesh:
        eng = ServingEngine(model, params,
                            ServeConfig(batch_size=2, max_prompt=8,
                                        max_new_tokens=4, keep_logits=True))
        rids = [eng.submit(p) for p in _prompts(cfg, [3, 6, 8], seed=5)]
        comps = {c.rid: c for c in eng.run()}
    assert sorted(eng.served_logits) == sorted(rids)
    for rid in rids:
        rows = np.stack(eng.served_logits[rid])
        assert rows.shape == (len(comps[rid].tokens), cfg.vocab)
        assert np.isfinite(rows).all()
        np.testing.assert_array_equal(rows.argmax(-1), comps[rid].tokens)


def test_engine_eos_frees_slot(served_model):
    cfg, minfo, model, params = served_model
    with minfo.mesh:
        eng = ServingEngine(model, params,
                            ServeConfig(batch_size=1, max_prompt=8,
                                        max_new_tokens=5))
        p = _prompts(cfg, [4], seed=5)[0]
        eng.submit(p)
        first = eng.run()[0].tokens[0]      # greedy first token
        eng.submit(p, eos_id=int(first))
        c = eng.run()[0]
    assert c.finish_reason == "eos" and len(c.tokens) == 1


# ---------------------------------------------------------------------------
# serve_bench --json schema: the committed BENCH_serve.json is the tracked
# perf record — its key structure must not drift from what the bench writes.
# ---------------------------------------------------------------------------
def test_serve_bench_json_schema_matches_committed(tmp_path):
    bench_dir = os.path.join(os.path.dirname(__file__), "..", "benchmarks")
    sys.path.insert(0, bench_dir)
    try:
        import serve_bench
    finally:
        sys.path.remove(bench_dir)
    out = tmp_path / "bench.json"
    built = serve_bench.build_model(0)
    doc = serve_bench.run(requests=2, max_new_tokens=2, batch_size=2,
                          max_prompt=8, smoke=True, seed=0,
                          json_path=str(out), built=built)
    with open(os.path.join(bench_dir, "..", "BENCH_serve.json")) as f:
        committed = json.load(f)
    assert json.loads(out.read_text()) == doc
    assert set(doc) == set(committed)
    for section in ("config", "wall"):
        assert set(doc[section]) == set(committed[section]), section
    # every lane row (fleet included) carries the same metric columns
    rows = list(doc["groups"].values()) + list(committed["groups"].values())
    want = set(committed["groups"]["fleet"])
    for row in rows:
        assert set(row) == want
    # ad-hoc runs emit the evidence blocks as None placeholders; the
    # committed record must carry all three filled
    assert doc["ab"] is None and doc["smoke_baseline"] is None
    assert doc["width_sweep"] is None
    ab = committed["ab"]
    assert set(ab) >= {"arms", "repeat"}
    assert len(ab["arms"]) >= 3                     # ≥3 KV formats paired
    assert "bf16" in ab["arms"] or "posit16" in ab["arms"]
    for arm in ab["arms"].values():
        assert {"us_per_token", "nj_per_token"} <= set(arm)
    sweep = committed["width_sweep"]
    assert set(sweep) >= {"posit8", "posit16"}
    for row in sweep.values():
        assert set(row) == {"first_divergence", "match_fraction"}
    sb = committed["smoke_baseline"]
    assert set(sb) == {"config", "fleet"}
    assert set(sb["config"]) == set(committed["config"])
    assert "us_per_token" in sb["fleet"]


def test_serve_policy_validation_and_lane_keys():
    with pytest.raises(ValueError):
        ServePolicy(weights="fp16")                 # IEEE → native dtypes
    with pytest.raises((KeyError, ValueError)):
        ServePolicy(kv="posit-bogus")
    p = ServePolicy(weights="posit16", kv="posit8")
    assert p.kv_bits == 8 and "kv=posit8" in p.lane
    assert dataclasses.replace(p) == p and hash(p) == hash(p)
    qp = p.quant_policy()
    assert qp.weights == "posit16" and qp.kv_cache == "posit8"
    assert ServePolicy.from_quant_policy(qp) == p
