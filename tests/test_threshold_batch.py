"""The batched 2-means threshold of the R-peak fold.

One launch clusters up to ``THRESHOLD_ROWS`` windows, each over its own
reservoir, a window warm-starting from the previous window of its stream
inside the same launch.  Contract: every window's centroids are bit for bit
those of the same program run one window per launch, the masked rows keep
``kmeans_1d``'s semantics on the valid prefix, streaming at any
``max_batch`` equals offline ``detect_rpeaks``, and each format compiles one
threshold program whatever the reservoirs' lengths.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps.bayeslope import (RESERVOIR_SIZE, THRESHOLD_ROWS, RPeakFold,
                                  _threshold_fn, _threshold_fn_cached,
                                  detect_rpeaks,
                                  fold_thresholds)
from repro.apps.kmeans import kmeans_1d
from repro.core.arith import Arith
from repro.data.biosignals import ecg_stream_signal, ragged_chunks
from repro.stream import StreamEngine, rpeak_pipeline

W = 500
# a window of these scores drives the format's centroids non-finite
# (posit arithmetic saturates: no window can)
BLOW_UP = {"fp32": np.finfo(np.float32).max, "fp8e4m3": 1e4}
BLOW_AT = 30


def _batches(fmt, rng):
    """Two batches of (stream, scores): 64 windows of stream ``a`` alone —
    one chain 64 deep, its window ``BLOW_AT`` one that blows the format's
    centroids up where it can — then 100 windows of ``a``, ``b`` and ``c``
    interleaved, which take two launches.  Window lengths vary, so the
    reservoirs pass through lengths from 100 to 500."""
    def scores(n):
        return (rng.uniform(0, 1, n) ** 3).astype(np.float32)

    first = [("a", scores(int(rng.integers(100, W + 1))))
             for _ in range(THRESHOLD_ROWS)]
    # five windows' worth: the whole reservoir
    first[BLOW_AT] = ("a", np.full(5 * W, BLOW_UP.get(fmt, 1.0), np.float32))
    order = ["a"] * 40 + ["b"] * 30 + ["c"] * 30
    rng.shuffle(order)
    second = [(k, scores(int(rng.integers(100, W + 1)))) for k in order]
    return [first, second]


def _deepest(keys):
    return max(keys.count(k) for k in set(keys))


@functools.lru_cache(maxsize=None)
def _kmeans_ref(fmt, warm):
    ar = Arith.make(fmt)
    if warm:
        return jax.jit(lambda x, init: kmeans_1d(ar, x, init=init))
    return jax.jit(lambda x: kmeans_1d(ar, x))


def _bits(c):
    return np.asarray(c, np.float32).view(np.uint32)


@pytest.mark.parametrize("fmt", ["posit8", "posit10", "posit16", "fp32",
                                 "fp8e4m3"])
def test_batched_thresholds_equal_one_row_launches(fmt):
    """Every window's centroids and threshold from the batched launches
    equal, bit for bit, those of the same program one window per launch;
    so do the confirmed peaks."""
    ar = Arith.make(fmt)
    batches = _batches(fmt, np.random.default_rng(7))
    batched = {k: RPeakFold() for k in "abc"}
    single = {k: RPeakFold() for k in "abc"}
    launches, lengths, checked = [], set(), 0
    for b, batch in enumerate(batches):
        rows = [(batched[k], batched[k].stage(s)) for k, s in batch]
        fold_thresholds(ar, rows,
                        lambda t0, t1, n, depth: launches.append((n, depth)))
        for i, ((k, s), (_, st)) in enumerate(zip(batch, rows)):
            fold = single[k]
            warm = fold.cents
            one = fold.stage(s)
            fold_thresholds(ar, [(fold, one)])
            lengths.add(len(one.reservoir))
            assert np.array_equal(_bits(st.cents), _bits(one.cents)), (k, i)
            assert np.array_equal(st.thr, one.thr, equal_nan=True)
            if b == 0 and (i < 5 or BLOW_AT <= i <= BLOW_AT + 1):
                # kmeans_1d on the valid prefix, warm-started from the
                # stream's last centroids where all were finite
                x = jnp.asarray(one.reservoir)
                ref = np.asarray(_kmeans_ref(fmt, warm is not None)(
                    *((x,) if warm is None else (x, jnp.asarray(warm)))))
                if fmt == "fp32":     # one wide sum, in XLA's order
                    np.testing.assert_allclose(one.cents, ref, rtol=1e-6)
                else:
                    assert np.array_equal(_bits(one.cents), _bits(ref)), i
                checked += 1
            if b == 0 and i == BLOW_AT + 1 and fmt in BLOW_UP:
                # the window after the blow-up started cold, in the launch
                # as one window at a time
                assert warm is None
                assert not np.all(np.isfinite(rows[BLOW_AT][1].cents))
        for k in "abc":
            while batched[k].staged:
                assert np.array_equal(batched[k].advance(),
                                      single[k].advance())
    keys = [k for k, _ in batches[1]]
    assert launches == [(64, 64), (64, _deepest(keys[:64])),
                        (36, _deepest(keys[64:]))]
    assert checked == 7
    assert min(lengths) <= 200 and max(lengths) == RESERVOIR_SIZE
    for k in "abc":
        assert batched[k].peaks == single[k].peaks


@pytest.mark.parametrize("max_batch", [1, 7, 64])
def test_streaming_at_any_batch_equals_offline(max_batch):
    """Three patients' records, raggedly interleaved, pumped only when a
    batch fills and at the end: each one's streamed peaks equal offline
    ``detect_rpeaks``; every dispatch makes one threshold launch per
    ``THRESHOLD_ROWS`` windows and the counters sum the windows."""
    fmt = "posit10"
    ar = Arith.make(fmt)
    rng = np.random.default_rng(max_batch)
    eng = StreamEngine({"rpeak": rpeak_pipeline()}, max_batch=max_batch)
    sigs, queues = {}, []
    for i in range(3):
        sig, _ = ecg_stream_signal(16.0, seed=300 + i, n_phases=3)
        pid = f"p{i}"
        sigs[pid] = sig
        eng.register_patient(pid, "rpeak", fmt=fmt)
        queues.append((pid, list(ragged_chunks(sig[None, :], rng, 50, 900))))
    while any(q for _, q in queues):
        pid, chunks = queues[int(rng.integers(len(queues)))]
        if chunks:
            eng.ingest(pid, "rpeak", "ecg", chunks.pop(0))
    eng.drain()
    eng.finalize_all()
    for pid, sig in sigs.items():
        assert eng.tracker_for(pid, "rpeak").peaks == detect_rpeaks(ar, sig)
    windows = sum(len(s) // W for s in sigs.values())
    m = eng.metrics
    assert m.get("tracker_threshold_rows_total").total() == windows
    dispatches = sum(g.batches for g in eng.ledger.stats.values())
    assert m.get("tracker_threshold_launches_total").total() == dispatches


def test_one_threshold_program_per_format_whatever_the_reservoir():
    """Reservoirs of 100 to 500 entries, warm and cold, one and many rows,
    in two formats: each format compiles one threshold program, and it
    takes the fixed shape only."""
    rng = np.random.default_rng(5)
    for fmt in ("posit8", "posit16"):
        before = _threshold_fn_cached.cache_info().misses
        eng = StreamEngine({"rpeak": rpeak_pipeline()}, max_batch=3)
        for p in range(2):
            eng.register_patient(f"q{p}", "rpeak", fmt=fmt)
        for _ in range(7):
            for p in range(2):
                eng.ingest(f"q{p}", "rpeak", "ecg",
                           rng.normal(size=(1, W)).astype(np.float32))
            eng.pump()
        fold = RPeakFold()
        fold.push(Arith.make(fmt), rng.uniform(0, 1, 137).astype(np.float32))
        assert _threshold_fn_cached.cache_info().misses - before <= 1
        fn = _threshold_fn(fmt)
        assert fn is _threshold_fn(fmt)
        R = THRESHOLD_ROWS
        with pytest.raises(TypeError):
            fn(np.zeros((R, 137), np.float32), np.zeros(R, np.int32),
               np.zeros((R, 2), np.float32), np.zeros(R, bool),
               np.full(R, -1, np.int32), np.int32(1))
