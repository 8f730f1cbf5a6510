"""The without-replacement sampler's top-m: ``top_k_rows`` returns exactly
``jax.lax.top_k``'s values and indices however many ``vmap``s sit above
it, so folding batch axes into rows changes no sampled set."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.estimators import (
    gumbel_noise,
    sample_set_from_mask,
    sample_set_batch,
    top_k_rows,
)

N, M = 257, 5


def _scores(seed, shape, alive=1.0):
    """Gumbel-like scores, -inf where not alive (the sampler's operand)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.gumbel(k1, shape)
    keep = jax.random.bernoulli(k2, alive, shape)
    return jnp.where(keep, x, -jnp.inf)


def _assert_same(got, want):
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def _unbatched(x):
    return top_k_rows(x[0, 0], M), jax.lax.top_k(x[0, 0], M)


def _vmap(x):
    return (jax.vmap(lambda r: top_k_rows(r, M))(x[0]),
            jax.lax.top_k(x[0], M))


def _vmap_vmap(x):
    return (jax.vmap(jax.vmap(lambda r: top_k_rows(r, M)))(x),
            jax.lax.top_k(x, M))


def _vmap_vmap_inner_unbatched(x):
    """The inner vmap maps over something the operand does not depend on."""
    got = jax.vmap(lambda r: jax.vmap(lambda _: top_k_rows(r, M))(
        jnp.arange(x.shape[1])))(x[:, 0])
    want = jax.lax.top_k(x[:, 0], M)
    want = tuple(jnp.broadcast_to(w[:, None], got[i].shape)
                 for i, w in enumerate(want))
    return got, want


@pytest.mark.parametrize("alive", [1.0, 0.01], ids=["dense", "under_m_alive"])
@pytest.mark.parametrize("case", [_unbatched, _vmap, _vmap_vmap,
                                  _vmap_vmap_inner_unbatched],
                         ids=lambda f: f.__name__.lstrip("_"))
def test_top_k_rows_equals_lax_top_k(case, alive):
    x = _scores(0, (3, 4, N), alive)
    if alive < 1.0:            # some rows keep fewer than M finite entries
        assert int(jnp.min(jnp.sum(jnp.isfinite(x), -1))) < M
    got, want = jax.jit(case)(x)
    _assert_same(got, want)


@pytest.mark.parametrize("alive", [0.5, 0.01], ids=["half", "under_m_alive"])
def test_nested_vmap_sampler_matches_per_row_loop(alive):
    """``sample_set_from_mask`` under the lattice's vmap(vmap(.)) draws the
    same (idx, valid) as a loop of unbatched calls, and those are the
    plain Gumbel top-m of the same noise."""
    G, S = 3, 4
    keys = jax.random.split(jax.random.PRNGKey(7), G * S).reshape(G, S, 2)
    masks = jax.random.bernoulli(jax.random.PRNGKey(8), alive, (G, N))
    draw = jax.jit(jax.vmap(jax.vmap(
        lambda k, mk: sample_set_from_mask(k, mk, M), in_axes=(0, None))))
    idx, valid = draw(keys, masks)
    assert idx.shape == valid.shape == (G, S, M) and idx.dtype == jnp.int32
    assert bool(jnp.all(valid)) == (alive > 0.1)
    for g in range(G):
        for s in range(S):
            one = sample_set_from_mask(keys[g, s], masks[g], M)
            _assert_same((idx[g, s], valid[g, s]), one)
            scores = jnp.where(masks[g], gumbel_noise(keys[g, s], N), -jnp.inf)
            vals, want = jax.lax.top_k(scores, M)
            np.testing.assert_array_equal(np.asarray(idx[g, s]),
                                          np.asarray(want))
            np.testing.assert_array_equal(np.asarray(valid[g, s]),
                                          np.isfinite(np.asarray(vals)))


def test_sample_set_batch_under_vmap_matches_unbatched():
    """The filter's (n_samples, m) draw, vmapped over guesses."""
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    mask = jax.random.bernoulli(jax.random.PRNGKey(4), 0.3, (N,))
    got = jax.jit(jax.vmap(lambda k: sample_set_batch(k, mask, M, 6)))(keys)
    for g in range(3):
        _assert_same((got[0][g], got[1][g]),
                     sample_set_batch(keys[g], mask, M, 6))
