"""Format-parametrized DSP: iterative radix-2 FFT, PSD, spectral statistics,
MFCC — every arithmetic op rounded to the chosen format through ``Arith``
(the Universal-library simulation methodology of the paper, §IV).

The FFT here is the paper's §VI-B energy kernel: 4096-point, the hot spot of
the cough-detection application (~50% of runtime).

All per-call table construction (bit-reversal permutation, per-stage
twiddles, mel filterbank, DCT basis) is cached in an :class:`FFTPlan` /
table cache keyed on (size, format, dtype): tables are pre-rounded through
the target format once and embedded as trace-time constants, so re-tracing
a pipeline no longer rebuilds them in Python nor re-traces a rounding chain
per table per compile.

Exact butterfly identities (used by ``rfft_format`` to skip provably
redundant rounded work while staying bit-identical to the naive all-ops
path):

* rounding is idempotent — ``rnd`` maps a float to lattice bits with every
  sub-LSB bit cleared, so a second ``rnd`` at the same scale is a no-op;
* for a real input the stage-1 twiddle is (1, ±0) and the imaginary plane
  is exactly zero, so ``t = w ⊗ o`` collapses to ``t = o`` and stage 1 is
  a pure real add/sub butterfly; stage 2 collapses to
  ``t = (wr·o_re, wi·o_re)`` with ``u_im/v_im = ±t_im``
  (``rnd(-x) = -rnd(x)``: both lattices are symmetric under negation);
* a real input's power spectrum reads only bins 0..n/2, and those depend
  on every butterfly except the final stage's v[1:] outputs.

The stage-1/2 collapses are applied only for posit formats: posits cannot
produce ±Inf, so finite inputs keep every intermediate finite and the
identities hold unconditionally, whereas IEEE formats overflow mid-FFT and
the naive path's ``(-0)·(±Inf) = NaN`` poisoning must be reproduced with
honest butterflies.  The final-stage pruning is exact for every format.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arith import Arith, get_fused_kernels, get_round_backend
from repro.core.posit import round_to_posit


# ---------------------------------------------------------------------------
# FFT plan cache
# ---------------------------------------------------------------------------

class FFTPlan:
    """Cached tables for one (n, format, dtype) FFT.

    ``stages[s]`` holds the stage-(s+1) twiddle factors ``(wr, wi)`` as
    numpy constants pre-rounded through the target format — identical
    values to rounding ``cos/sin`` on every call, without the per-trace
    rounding chains.  No bit-reversal table: the stage loops below use
    the self-sorting Stockham layout and never permute.
    """

    def __init__(self, n: int, fmt_name: str, dtype_name: str):
        assert n & (n - 1) == 0, "power-of-two FFT"
        self.n = n
        self.levels = n.bit_length() - 1
        ar = Arith.make(fmt_name)
        self.stages: List[Tuple[np.ndarray, np.ndarray]] = []
        for s in range(1, self.levels + 1):
            m = 1 << s
            ang = -2.0 * np.pi * np.arange(m // 2) / m
            self.stages.append((_rounded_table(ar, np.cos(ang), dtype_name),
                                _rounded_table(ar, np.sin(ang), dtype_name)))


def _rounded_table(ar: Arith, table: np.ndarray,
                   dtype_name: str) -> np.ndarray:
    """A constant table rounded onto ``ar``'s lattice, as numpy.

    Tables are built lazily on first use, which may be inside a jit trace:
    escape it so they materialize as real constants.  Posit tables round
    through the plain jnp path, bit-identical to every round backend: a
    Pallas kernel cannot be traced under compile-time evaluation (its
    constants would be captured as arrays, which Pallas refuses).
    """
    with jax.ensure_compile_time_eval():
        x = jnp.asarray(table, jnp.dtype(dtype_name))
        if ar.is_posit:
            return np.asarray(round_to_posit(x, ar.fmt))
        return np.asarray(ar.rnd(x))


@functools.lru_cache(maxsize=None)
def get_fft_plan(n: int, fmt_name: str, dtype_name: str) -> FFTPlan:
    return FFTPlan(n, fmt_name, dtype_name)


def _twiddle_mul(ar: Arith, o_re, o_im, wr, wi):
    """The complex twiddle product ``t = w ⊗ o``.

    Default: 4 mul + 2 add, each rounded (the seed butterfly).  Quire mode:
    each component is ONE fused two-term accumulation — two QMADDs and a
    single QROUND via ``Arith.fdot2`` (``−wi`` is exact: posit lattices are
    symmetric under negation, so the pre-rounded twiddle negates in place).
    """
    if ar.quire:
        return (ar.fdot2(wr, o_re, -wi, o_im),
                ar.fdot2(wr, o_im, wi, o_re))
    return (ar.sub(ar.mul(wr, o_re), ar.mul(wi, o_im)),
            ar.add(ar.mul(wr, o_im), ar.mul(wi, o_re)))


def _butterfly(ar: Arith, e_re, e_im, o_re, o_im, wr, wi):
    """t = w ⊗ o (rounded per ``_twiddle_mul``); u = e + t; v = e − t."""
    t_re, t_im = _twiddle_mul(ar, o_re, o_im, wr, wi)
    u_re = ar.add(e_re, t_re)
    u_im = ar.add(e_im, t_im)
    v_re = ar.sub(e_re, t_re)
    v_im = ar.sub(e_im, t_im)
    return u_re, u_im, v_re, v_im


# Stockham stage layout.  State is (..., L, R) "transposed" early and
# (..., R, L) "natural" late, where L is the sub-DFT length completed so
# far and R = n / L; row r of the natural layout holds DFT_L of the
# stride-R subsequence of the input starting at r.  Both layouts split
# butterfly partners and write u/v as CONTIGUOUS blocks — unlike the
# classic in-place DIT indexing, whose per-stage group reshuffles cost
# more than the butterfly arithmetic itself on CPU.  The one
# transposed→natural switch (a single transpose per FFT) happens when the
# transposed split runs would drop below _MIN_RUN elements; after it the
# natural joins have runs of L ≥ _MIN_RUN.
_MIN_RUN = 64


def _stage_split(z_re, z_im, R: int, transposed: bool):
    if transposed:  # (..., L, R): partners along the last axis
        return (z_re[..., : R // 2], z_im[..., : R // 2],
                z_re[..., R // 2:], z_im[..., R // 2:])
    # natural (..., R, L): partners along the row axis
    return (z_re[..., : R // 2, :], z_im[..., : R // 2, :],
            z_re[..., R // 2:, :], z_im[..., R // 2:, :])


def _stage_join(u, v, transposed: bool):
    return jnp.concatenate([u, v], axis=-2 if transposed else -1)


def _stage_tw(w_np: np.ndarray, transposed: bool) -> jax.Array:
    w = jnp.asarray(w_np)
    return w[:, None] if transposed else w


def _to_natural(z_re, z_im, transposed: bool):
    if transposed:
        return jnp.swapaxes(z_re, -1, -2), jnp.swapaxes(z_im, -1, -2)
    return z_re, z_im


# ---------------------------------------------------------------------------
# Fused stage loop: the whole (batch, n) plane of one Stockham stage in one
# launch.  State is STACKED — z has shape (2, ..., L, R) with axis 0 the
# (re, im) planes — so each stage is three rounded calls instead of ten:
#
#   P  = rnd([wr·o_re, wi·o_im, wr·o_im, wi·o_re])   (4 half-planes, 1 call)
#   t  = rnd([P0 − P1, P2 + P3])                     (t_re, t_im, 1 call)
#   z' = rnd(concat([e + t, e − t]))                 (u ++ v: the stage JOIN
#                                                     fuses into the rounding)
#
# Identical elementary rounded ops in the identical order as `_butterfly` —
# elementwise chains are bitwise deterministic, so the fused loop is
# bit-identical to the unfused oracle (tests/test_fused_backend.py).  Under
# the pallas round backend the posit stage runs as one `posit_butterfly`
# kernel launch over the whole plane, twiddles broadcast from the plan
# constants (interpret-mode fallback off-TPU).
# ---------------------------------------------------------------------------

def _fused_stage(ar: Arith, z: jax.Array, wr_np: np.ndarray,
                 wi_np: np.ndarray, R: int, tr: bool) -> jax.Array:
    nb = z.ndim - 3                        # batch dims between stack and L/R
    if tr:
        e, o = z[..., : R // 2], z[..., R // 2:]
    else:
        e, o = z[..., : R // 2, :], z[..., R // 2:, :]
    if ar.quire:
        # quire arm: the twiddle join is two fused 2-term accumulations per
        # output (one rounding each) instead of the 6-op rounded cmul — the
        # same elementary ops in the same order as the unfused quire
        # butterfly, so fused≡unfused bit-identity holds here too.  The
        # Pallas butterfly kernel bakes in per-op rounding and is bypassed.
        shp = (*([1] * nb), -1, 1) if tr else (*([1] * nb), 1, -1)
        wr = jnp.asarray(wr_np).reshape(shp)
        wi = jnp.asarray(wi_np).reshape(shp)
        t = jnp.stack(_twiddle_mul(ar, o[0], o[1], wr, wi))
        return ar.rnd(jnp.concatenate([e + t, e - t], axis=-2 if tr else -1))
    if get_round_backend() == "pallas":
        from repro.kernels.posit_round import posit_butterfly
        shp = (*([1] * nb), -1, 1) if tr else (*([1] * nb), 1, -1)
        wr = jnp.asarray(wr_np).reshape(shp)
        wi = jnp.asarray(wi_np).reshape(shp)
        u_re, u_im, v_re, v_im = posit_butterfly(
            e[0], e[1], o[0], o[1], wr, wi, ar.fmt)
        ax = -2 if tr else -1
        return jnp.stack([jnp.concatenate([u_re, v_re], axis=ax),
                          jnp.concatenate([u_im, v_im], axis=ax)])
    rnd = ar.rnd
    # products without gathering o: [wr·o_re, wi·o_im] = [wr, wi]⊙o and
    # [wi·o_re, wr·o_im] = [wi, wr]⊙o, so P = [P0, P1, P3, P2] (the swapped
    # t_im order is free — f32 addition commutes bitwise)
    w2 = jnp.asarray(np.stack([wr_np, wi_np]))
    w2f = jnp.asarray(np.stack([wi_np, wr_np]))
    shp = (2, *([1] * nb), -1, 1) if tr else (2, *([1] * nb), 1, -1)
    P = rnd(jnp.concatenate([w2.reshape(shp) * o, w2f.reshape(shp) * o],
                            axis=0))
    t = rnd(jnp.stack([P[0] - P[1], P[3] + P[2]]))
    return rnd(jnp.concatenate([e + t, e - t], axis=-2 if tr else -1))


def _fused_final_rstage(ar: Arith, z: jax.Array, plan: FFTPlan
                        ) -> Tuple[jax.Array, jax.Array]:
    """Pruned final stage of the real-input split (natural layout): only u
    (bins 0..n/2−1) and v[0] (Nyquist) are computed — same stacked shapes,
    same rounded ops as the kept lanes of a full stage."""
    rnd = ar.rnd
    wr_np, wi_np = plan.stages[-1]
    wr, wi = jnp.asarray(wr_np), jnp.asarray(wi_np)
    e_re, o_re = z[0, ..., 0, :], z[0, ..., 1, :]
    e_im, o_im = z[1, ..., 0, :], z[1, ..., 1, :]
    if ar.quire:
        t = jnp.stack(_twiddle_mul(ar, o_re, o_im, wr, wi))
    else:
        P = rnd(jnp.stack([wr * o_re, wi * o_im, wr * o_im, wi * o_re]))
        t = rnd(jnp.stack([P[0] - P[1], P[2] + P[3]]))
    u = rnd(jnp.stack([e_re + t[0], e_im + t[1]]))
    ny = rnd(jnp.stack([e_re[..., :1] - t[0][..., :1],
                        e_im[..., :1] - t[1][..., :1]]))
    return (jnp.concatenate([u[0], ny[0]], axis=-1),
            jnp.concatenate([u[1], ny[1]], axis=-1))


def fft_format(ar: Arith, re: jax.Array, im: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """Iterative radix-2 FFT over the last axis, every op rounded.

    Twiddles are stored in the target format (table-based, as on PHEE).
    Self-sorting Stockham stage layout: the same butterflies on the same
    operand values as the classic bit-reversed DIT (bit-identical output),
    with no input permutation and contiguous stage splits/joins.  The
    fused stage loop (default) runs each stage as one launch over the
    whole plane; ``REPRO_FUSED_KERNELS=off`` selects the retained per-op
    oracle — bit-identical either way.
    """
    n = re.shape[-1]
    plan = get_fft_plan(n, ar.name, str(re.dtype))
    if not (get_fused_kernels() and ar.is_posit):
        # IEEE/fp32: rounding is a single convert, so the per-op loop IS
        # the fused-optimal shape — XLA folds each butterfly into tight
        # loops, and the stacked regrouping only pays where the rounding
        # chain is ~30 integer ops per element (posits; measured 3.8×
        # SLOWER for fp16 when stacked)
        return _fft_unfused(ar, re, im, plan)
    z = ar.rnd(jnp.stack([re, im]))[..., None, :]   # (2, ..., L=1, n)
    tr = True
    for t, (wr_np, wi_np) in enumerate(plan.stages):
        R = n >> t
        if tr and R // 2 < _MIN_RUN:
            z = jnp.swapaxes(z, -1, -2)
            tr = False
        z = _fused_stage(ar, z, wr_np, wi_np, R, tr)
    if tr:
        z = jnp.swapaxes(z, -1, -2)                 # (2, ..., 1, n)
    z = z.reshape(2, *z.shape[1:-2], n)
    return z[0], z[1]


def _fft_unfused(ar: Arith, re: jax.Array, im: jax.Array, plan: FFTPlan
                 ) -> Tuple[jax.Array, jax.Array]:
    """The per-op stage loop (6 separately-rounded jnp ops per butterfly) —
    the retained oracle the fused loop is property-tested against."""
    n = re.shape[-1]
    zr = ar.rnd(re)[..., None, :]          # transposed start: (..., L=1, n)
    zi = ar.rnd(im)[..., None, :]
    tr = True
    for t, (wr_np, wi_np) in enumerate(plan.stages):
        R = n >> t
        if tr and R // 2 < _MIN_RUN:
            zr, zi = _to_natural(zr, zi, tr)
            tr = False
        wr, wi = _stage_tw(wr_np, tr), _stage_tw(wi_np, tr)
        e_re, e_im, o_re, o_im = _stage_split(zr, zi, R, tr)
        u_re, u_im, v_re, v_im = _butterfly(ar, e_re, e_im, o_re, o_im,
                                            wr, wi)
        zr = _stage_join(u_re, v_re, tr)
        zi = _stage_join(u_im, v_im, tr)
    zr, zi = _to_natural(zr, zi, tr)       # (..., 1, n) either way
    return (zr.reshape(*zr.shape[:-2], n), zi.reshape(*zi.shape[:-2], n))


def rfft_format(ar: Arith, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """FFT of a real last axis, returning only bins 0 .. n/2 (re, im).

    Bit-identical to ``fft_format(ar, x, 0)[..., :n//2+1]`` — the same
    rounded ops at every kept index (see module docstring for the exact
    identities) — while never materializing the imaginary plane before
    stage 2 and skipping the negative-frequency outputs of the final
    stage, which the power spectrum of a real signal never reads.

    The identity is unconditional for IEEE formats; for posit formats it
    assumes the input is NaR-free (any finite real window — posit
    rounding of a finite float is always finite).  A NaN sample would
    poison both planes in the naive path but only the real plane here.
    """
    n = x.shape[-1]
    plan = get_fft_plan(n, ar.name, str(x.dtype))
    if plan.levels < 3:  # tiny sizes: no stages left to prune
        re, im = fft_format(ar, x, jnp.zeros_like(x))
        return re[..., : n // 2 + 1], im[..., : n // 2 + 1]
    if get_fused_kernels() and ar.is_posit:
        return _rfft_fused(ar, x, plan)
    return _rfft_unfused(ar, x, plan)


def _rfft_fused(ar: Arith, x: jax.Array, plan: FFTPlan
                ) -> Tuple[jax.Array, jax.Array]:
    """Stacked one-launch-per-stage realization of the posit rfft split —
    bit-identical to ``_rfft_unfused`` (same collapses, same rounded ops).
    IEEE formats never come here: their honest-poisoning butterflies stay
    on the per-op loop, which is their fused-optimal shape (see
    ``fft_format``)."""
    n = x.shape[-1]
    rnd = ar.rnd
    tr = True
    zr = rnd(x)[..., None, :]              # transposed start: (..., 1, n)
    # stage 1: pure real add/sub butterfly, join fused into the rounding
    e, o = zr[..., : n // 2], zr[..., n // 2:]
    zr = rnd(jnp.concatenate([e + o, e - o], axis=-2))
    # stage 2: t = (wr·o, wi·o); u_im = t_im, v_im = −t_im (exact)
    R = n >> 1
    wr = jnp.asarray(plan.stages[1][0])[:, None]
    wi = jnp.asarray(plan.stages[1][1])[:, None]
    e, o = zr[..., : R // 2], zr[..., R // 2:]
    t = rnd(jnp.stack([wr * o, wi * o]))
    z = jnp.stack([rnd(jnp.concatenate([e + t[0], e - t[0]], axis=-2)),
                   jnp.concatenate([t[1], -t[1]], axis=-2)])
    start = 2
    for t in range(start, plan.levels - 1):
        R = n >> t
        if tr and R // 2 < _MIN_RUN:
            z = jnp.swapaxes(z, -1, -2)
            tr = False
        z = _fused_stage(ar, z, *plan.stages[t], R, tr)
    if tr:
        z = jnp.swapaxes(z, -1, -2)
    return _fused_final_rstage(ar, z, plan)


def _rfft_unfused(ar: Arith, x: jax.Array, plan: FFTPlan
                  ) -> Tuple[jax.Array, jax.Array]:
    n = x.shape[-1]
    zr = ar.rnd(x)[..., None, :]           # transposed start: (..., 1, n)
    tr = True

    if ar.is_posit:
        # Posits have no ±Inf and saturate instead of overflowing, so a
        # finite real input keeps every intermediate finite and the exact
        # stage collapses below hold unconditionally (a NaR input would
        # poison only the real plane here but both planes in the naive
        # path — real sensor windows are finite).
        # stage 1: w = (1, +0) → t = o; pure real add/sub butterfly,
        # imaginary plane stays exactly zero
        e_re, o_re = zr[..., : n // 2], zr[..., n // 2:]
        zr = _stage_join(ar.add(e_re, o_re), ar.sub(e_re, o_re), tr)
        # stage 2: im-plane inputs are zero → t = (wr·o_re, wi·o_re),
        # u_im = t_im, v_im = -t_im (both lattices negate exactly)
        R = n >> 1
        wr = _stage_tw(plan.stages[1][0], tr)
        wi = _stage_tw(plan.stages[1][1], tr)
        e_re, o_re = zr[..., : R // 2], zr[..., R // 2:]
        t_re = ar.mul(wr, o_re)
        t_im = ar.mul(wi, o_re)
        zr = _stage_join(ar.add(e_re, t_re), ar.sub(e_re, t_re), tr)
        zi = _stage_join(t_im, -t_im, tr)
        start = 2
    else:
        # IEEE formats overflow to ±Inf (or NaN) mid-FFT, and the naive
        # path's (-0)·(±Inf) = NaN poisoning must be reproduced exactly:
        # run the honest butterflies on an explicit zero imaginary plane.
        zi = jnp.zeros_like(zr)
        start = 0

    for t in range(start, plan.levels - 1):
        R = n >> t
        if tr and R // 2 < _MIN_RUN:
            zr, zi = _to_natural(zr, zi, tr)
            tr = False
        wr_np, wi_np = plan.stages[t]
        wr, wi = _stage_tw(wr_np, tr), _stage_tw(wi_np, tr)
        e_re, e_im, o_re, o_im = _stage_split(zr, zi, R, tr)
        u_re, u_im, v_re, v_im = _butterfly(ar, e_re, e_im, o_re, o_im,
                                            wr, wi)
        zr = _stage_join(u_re, v_re, tr)
        zi = _stage_join(u_im, v_im, tr)

    # final stage (R = 2, natural layout): only u (bins 0..n/2-1) and
    # v[0] (the Nyquist bin) are non-redundant for a real input — v[1:]
    # is never computed
    zr, zi = _to_natural(zr, zi, tr)
    wr_np, wi_np = plan.stages[-1]
    wr, wi = jnp.asarray(wr_np), jnp.asarray(wi_np)
    e_re, o_re = zr[..., 0, :], zr[..., 1, :]
    e_im, o_im = zi[..., 0, :], zi[..., 1, :]
    t_re, t_im = _twiddle_mul(ar, o_re, o_im, wr, wi)
    u_re = ar.add(e_re, t_re)
    u_im = ar.add(e_im, t_im)
    ny_re = ar.sub(e_re[..., :1], t_re[..., :1])
    ny_im = ar.sub(e_im[..., :1], t_im[..., :1])
    return (jnp.concatenate([u_re, ny_re], axis=-1),
            jnp.concatenate([u_im, ny_im], axis=-1))


def power_spectrum(ar: Arith, x: jax.Array) -> jax.Array:
    """|FFT|² of a real signal (first N/2+1 bins, via the rfft split)."""
    re, im = rfft_format(ar, x)
    return ar.add(ar.mul(re, re), ar.mul(im, im))


def spectral_features(ar: Arith, psd: jax.Array, sr: float) -> jax.Array:
    """Centroid, rolloff (85%), flatness-proxy, band energies.

    One rounded prefix-sum pass serves both the rolloff threshold AND the
    total spectral energy (its last prefix) — the total is no longer a
    second rounded reduction over the same bins.  The centroid numerator
    is a quire-fused ``Arith.matmul`` row (posit: one rounding; IEEE:
    per-MAC, bit-identical to the former mul+sum chain).
    """
    n = psd.shape[-1]
    freqs = jnp.asarray(np.linspace(0, sr / 2, n), psd.dtype)
    # rolloff threshold math in the target arithmetic (format parity):
    # rounded prefix energies against a rounded 0.85·total threshold
    cum = ar.cumsum(psd, axis=-1)
    total = jnp.maximum(cum[..., -1], 1e-20)
    centroid = ar.div(ar.matmul(psd, freqs[:, None])[..., 0], total)
    thr = ar.mul(ar.rnd(jnp.asarray(0.85, psd.dtype)), cum[..., -1:])
    roll_idx = jnp.argmax(cum >= thr, axis=-1)
    rolloff = freqs[roll_idx]
    # 4 log-spaced band energies (rounded ratios)
    bands = []
    edges = np.geomspace(1, n - 1, 5).astype(int)
    for i in range(4):
        e = ar.sum(psd[..., edges[i]:edges[i + 1]], axis=-1)
        bands.append(ar.div(e, total))
    return jnp.stack([centroid, rolloff, *bands], axis=-1)


# ---------------------------------------------------------------------------
# Cached, pre-rounded feature tables (mel filterbank, DCT basis)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _dct_basis(n: int, k: int, fmt_name: str, dtype_name: str) -> np.ndarray:
    basis = np.cos(np.pi / n * (np.arange(n) + 0.5)[None, :]
                   * np.arange(k)[:, None])
    return _rounded_table(Arith.make(fmt_name), basis, dtype_name)


@functools.lru_cache(maxsize=None)
def _mel_filterbank(n: int, sr: float, n_mel: int, fmt_name: str,
                    dtype_name: str) -> np.ndarray:
    fmax = sr / 2
    mel = lambda f: 2595 * np.log10(1 + f / 700)
    imel = lambda m: 700 * (10 ** (m / 2595) - 1)
    pts = imel(np.linspace(mel(20), mel(fmax), n_mel + 2))
    bins = np.clip((pts / fmax * (n - 1)).astype(int), 0, n - 1)
    fb = np.zeros((n_mel, n))
    for i in range(n_mel):
        a, b, c = bins[i], bins[i + 1], bins[i + 2]
        if b > a:
            fb[i, a:b] = np.linspace(0, 1, b - a, endpoint=False)
        if c > b:
            fb[i, b:c] = np.linspace(1, 0, c - b, endpoint=False)
    return _rounded_table(Arith.make(fmt_name), fb, dtype_name)


def _dct2(ar: Arith, x: jax.Array, k: int) -> jax.Array:
    basis = _dct_basis(x.shape[-1], k, ar.name, str(x.dtype))
    return ar.matmul(x, jnp.asarray(basis.T))


def mfcc(ar: Arith, psd: jax.Array, sr: float, n_mel: int = 20,
         n_coef: int = 13) -> jax.Array:
    """Mel-frequency cepstral coefficients from a (rounded) PSD.

    Filterbank and DCT-II rows run through ``Arith.matmul``: posit formats
    keep the quire semantics (one wide product per output, rounded once —
    the same bits as the previous rounded einsum), IEEE formats now round
    after every MAC like every other reduction (they have no quire; the
    former single-rounding einsum understated their accumulation error).
    """
    fbq = _mel_filterbank(psd.shape[-1], sr, n_mel, ar.name, str(psd.dtype))
    energies = ar.matmul(psd, jnp.asarray(fbq.T))
    log_e = ar.log(jnp.maximum(energies, 1e-20))
    return _dct2(ar, log_e, n_coef)


# time-domain features (IMU)

def zero_crossing_rate(ar: Arith, x: jax.Array) -> jax.Array:
    s = jnp.sign(x)
    flips = jnp.abs(jnp.diff(s, axis=-1)) > 1
    return ar.mean(flips.astype(x.dtype), axis=-1)


def kurtosis(ar: Arith, x: jax.Array) -> jax.Array:
    mu = ar.mean(x, axis=-1)
    d = ar.sub(x, mu[..., None])
    d2 = ar.mul(d, d)
    m2 = ar.mean(d2, axis=-1)
    m4 = ar.mean(ar.mul(d2, d2), axis=-1)
    return ar.div(m4, jnp.maximum(ar.mul(m2, m2), 1e-20))


def rms(ar: Arith, x: jax.Array) -> jax.Array:
    return ar.sqrt(ar.mean(ar.mul(x, x), axis=-1))
