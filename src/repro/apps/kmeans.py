"""k-means clustering in a chosen arithmetic format (BayeSlope's last stage;
the paper's example of an *unsupervised* workload whose dynamic range killed
fixed point)."""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core.arith import Arith


def kmeans_1d(ar: Arith, x: jax.Array, k: int = 2, iters: int = 12,
              init: jax.Array = None) -> jax.Array:
    """1-D k-means, all arithmetic rounded to the format. Returns centroids.

    ``init`` warm-starts the centroids (e.g. from the previous streaming
    window's solution) instead of the lo..hi linspace — the incremental
    2-means that powers the streaming R-peak threshold. Warm starts are
    rounded to the format first, so centroids carried across windows stay
    representable values of the window's arithmetic.
    """
    x = ar.rnd(x)
    if init is not None:
        cent = ar.rnd(jnp.asarray(init).astype(x.dtype))
    else:
        lo, hi = jnp.min(x), jnp.max(x)
        cent = ar.rnd(jnp.linspace(lo, hi, k).astype(x.dtype))
    for _ in range(iters):
        d = jnp.abs(ar.sub(x[:, None], cent[None, :]))
        assign = jnp.argmin(d, axis=1)
        new = []
        for j in range(k):
            m = assign == j
            cnt = jnp.maximum(m.sum(), 1).astype(x.dtype)
            # pre-scaled accumulation: divide members by the count, THEN sum
            # (keeps the running sum inside the format's range — IEEE formats
            # have no quire, so their sums round/overflow per-add)
            contrib = ar.div(jnp.where(m, x, 0.0), cnt)
            new.append(ar.sum(contrib, axis=-1))
        cent = jnp.stack(new)
    return cent


def kmeans_1d_rows(ar: Arith, x: jax.Array, valid: jax.Array,
                   init: jax.Array, warm: jax.Array, k: int = 2,
                   iters: int = 12) -> jax.Array:
    """``kmeans_1d`` on the ``valid`` entries of each row of ``x`` (R, N).

    Rows with ``warm`` (R,) set start from their ``init`` (R, k) row; the
    others from the linspace between their valid entries' min and max.
    Entries outside ``valid`` join no cluster and add an exact 0 to each
    centroid's sum — at the end of the row when the valid entries are a
    prefix, so the IEEE formats' sequential sums equal ``kmeans_1d``'s on
    that prefix.  Returns the (R, k) centroids.
    """
    x = ar.rnd(jnp.where(valid, x, 0.0))
    lo = jnp.min(jnp.where(valid, x, jnp.inf), axis=1)
    hi = jnp.max(jnp.where(valid, x, -jnp.inf), axis=1)
    cold = ar.rnd(jnp.linspace(lo, hi, k, axis=1).astype(x.dtype))
    cent = jnp.where(warm[:, None], ar.rnd(init.astype(x.dtype)), cold)
    for _ in range(iters):
        # argmin over the k distances as jnp.argmin takes it: the first
        # minimum wins, and a NaN is a minimum
        best = jnp.abs(ar.sub(x, cent[:, :1]))
        assign = jnp.zeros(x.shape, jnp.int32)
        for j in range(1, k):
            d = jnp.abs(ar.sub(x, cent[:, j:j + 1]))
            take = (d < best) | (jnp.isnan(d) & ~jnp.isnan(best))
            assign = jnp.where(take, j, assign)
            best = jnp.where(take, d, best)
        new = []
        for j in range(k):
            m = (assign == j) & valid
            cnt = jnp.maximum(m.sum(axis=1), 1).astype(x.dtype)[:, None]
            contrib = ar.div(jnp.where(m, x, 0.0), cnt)
            new.append(ar.sum(contrib, axis=-1))
        cent = jnp.stack(new, axis=1)
    return cent
