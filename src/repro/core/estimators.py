"""Sampling + expectation-estimation utilities for adaptive sampling.

The idealized DASH (Alg. 1) uses exact expectations E_{R~U(X)}[·]; the
practical algorithm (paper App. G) replaces them with Monte-Carlo
estimates over ``n_samples`` i.i.d. sets.  On a fleet these estimates are
computed by different replicas, so we also provide a *trimmed* reduction:
dropping the extreme quantiles makes the estimator robust both to
statistical outliers and to straggler replicas returning stale/partial
values (runtime/straggler.py wires that policy in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def gumbel_noise(key, n: int):
    """(n,) i.i.d. Gumbel noise — the ONE noise layout every Gumbel-top-k
    sampler draws from.  Distributed runners evaluate the same function
    with a replicated key and slice their local block, which is what
    makes their samples bitwise identical to the single-device ones."""
    u = jax.random.uniform(key, (n,), minval=1e-9, maxval=1.0 - 1e-9)
    return -jnp.log(-jnp.log(u))


def top_k_rows(x, m: int):
    """``jax.lax.top_k(x, m)`` over the last axis of ``(..., n)`` ``x``.

    XLA lowers a top-k on TPU to its ``TopK`` op only for rank-1 and
    rank-2 operands; any higher rank becomes a full sort of n.  Samplers
    sit under nested ``vmap``s (samples × guesses), so this helper folds
    every batch axis into one row axis: the body runs ``top_k`` on a
    ``(rows, n)`` view, and its batching rule moves each enclosing
    ``vmap``'s axis into those rows instead of adding a rank.  Values and
    indices are exactly ``lax.top_k``'s.
    """
    return _top_k_rows(m)(x)


@functools.lru_cache(maxsize=None)
def _top_k_rows(m: int):
    @jax.custom_batching.custom_vmap
    def rows(x):
        vals, idx = jax.lax.top_k(x.reshape(-1, x.shape[-1]), m)
        lead = x.shape[:-1] + (m,)
        return vals.reshape(lead), idx.reshape(lead)

    @rows.def_vmap
    def _fold(axis_size, in_batched, x):
        # vmap hands an unbatched operand straight through, so here x
        # always carries the new axis at the front: it becomes more rows.
        return rows(x), (True, True)

    return rows


def sample_set_from_mask(key, mask, m: int):
    """Uniformly sample ≤ m distinct elements of the alive ``mask``.

    Gumbel-top-k trick: taking the top-m of i.i.d. Gumbel noise restricted
    to the alive entries is a uniform without-replacement sample.  Returns
    (idx, valid): int32 (m,) indices and bool (m,) slot validity (invalid
    slots occur when fewer than m elements are alive).  The top-m goes
    through :func:`top_k_rows`, so under the lattice's nested ``vmap``s
    it stays XLA's rank-2 ``TopK`` rather than a full sort of n.
    """
    with jax.named_scope("repro.sample"):
        scores = jnp.where(mask, gumbel_noise(key, mask.shape[0]), -jnp.inf)
        vals, idx = top_k_rows(scores, m)
        return idx.astype(jnp.int32), jnp.isfinite(vals)


def sample_set_batch(key, mask, m: int, n_samples: int):
    """(n_samples, m) independent uniform set samples from ``mask``."""
    keys = jax.random.split(key, n_samples)
    return jax.vmap(lambda k: sample_set_from_mask(k, mask, m))(keys)


def trimmed_mean(vals, trim_frac: float = 0.0):
    """Symmetric trimmed mean along axis 0 (static trim count).

    ``trim_frac`` = fraction trimmed from EACH side.  With 0 it is the
    plain mean.  Used as the straggler/outlier-robust estimator for
    E[f_S(R)] (DESIGN.md §9).
    """
    m = vals.shape[0]
    t = int(m * trim_frac)
    if t == 0:
        return jnp.mean(vals, axis=0)
    svals = jnp.sort(vals, axis=0)
    return jnp.mean(svals[t : m - t], axis=0)


def masked_argmax(values, mask):
    """argmax of ``values`` restricted to ``mask`` (int32)."""
    neg = jnp.finfo(values.dtype).min
    return jnp.argmax(jnp.where(mask, values, neg)).astype(jnp.int32)
