"""Sample-batched filter-gain engine: kernel vs ref vs per-sample path,
for all three objective epilogues (regression / A-optimality / logistic)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dash import DashConfig, _estimate_elem_gains
from repro.core.objectives import (
    AOptimalityObjective,
    ClassificationObjective,
    RegressionObjective,
    normalize_columns,
)
from repro.kernels.filter_gains.ops import (
    aopt_filter_gains,
    filter_gains,
    logistic_filter_gains,
)
from repro.kernels.filter_gains.ref import (
    aopt_filter_gains_ref,
    filter_gains_ref,
    logistic_filter_gains_ref,
)
from repro.kernels.operands import aopt_operands

RNG = np.random.default_rng(0)


def _shared_and_deltas(d, k, m, b):
    """Random shared basis Q (d, k) and per-sample deltas D (m, d, b) ⊥ Q."""
    if k:
        Q, _ = np.linalg.qr(RNG.normal(size=(d, k)))
    else:
        Q = np.zeros((d, 1))
    D = []
    for _ in range(m):
        Di = RNG.normal(size=(d, max(b, 1)))
        Di = Di - Q @ (Q.T @ Di)
        Di, _ = np.linalg.qr(Di)
        D.append(Di[:, : max(b, 1)])
    return jnp.asarray(Q, jnp.float32), jnp.asarray(np.stack(D), jnp.float32)


@pytest.mark.parametrize("d,n,k,b,m", [
    (32, 64, 0, 1, 2),        # empty shared basis
    (100, 300, 7, 4, 5),      # n % block_n != 0 → padding
    (128, 128, 16, 8, 3),
    (257, 513, 5, 3, 8),      # everything misaligned
    (64, 1000, 32, 2, 4),
])
def test_filter_gains_kernel_matches_ref(d, n, k, b, m):
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    Q, D = _shared_and_deltas(d, k, m, b)
    R = jnp.asarray(RNG.normal(size=(m, d)), jnp.float32)
    csq = jnp.sum(X * X, axis=0)
    got = filter_gains(X, Q, D, R, csq, interpret=True)
    want = filter_gains_ref(X, Q, D, R, csq)
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_filter_gains_zero_delta_matches_marginal_gains():
    """With all-zero deltas every sample row reduces to the plain
    per-state marginal-gain oracle."""
    from repro.kernels.marginal_gains.ref import regression_gains_ref

    d, n, k, m = 48, 96, 6, 3
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    Q, _ = _shared_and_deltas(d, k, 1, 1)
    D = jnp.zeros((m, d, 4), jnp.float32)
    r = jnp.asarray(RNG.normal(size=(d,)), jnp.float32)
    R = jnp.broadcast_to(r, (m, d))
    csq = jnp.sum(X * X, axis=0)
    got = filter_gains_ref(X, Q, D, R, csq)
    want = regression_gains_ref(X, Q, r, csq)
    for i in range(m):
        np.testing.assert_allclose(np.asarray(got[i]), np.asarray(want),
                                   rtol=1e-5, atol=1e-6)


def _problem(d=80, n=50, kmax=10, **kw):
    rng = np.random.default_rng(7)
    X = normalize_columns(jnp.asarray(rng.normal(size=(d, n)), jnp.float32))
    y = jnp.asarray(rng.normal(size=(d,)), jnp.float32)
    return RegressionObjective(X, y, kmax=kmax, **kw)


@pytest.mark.parametrize("n_sel", [0, 3, 7])
def test_engine_estimate_matches_per_sample_path(n_sel):
    """_estimate_elem_gains via the engine == the per-sample vmap path."""
    obj_ps = _problem(use_filter_engine=False)
    obj_en = _problem(use_filter_engine=True)
    st = obj_ps.init()
    if n_sel:
        idx = jnp.arange(n_sel, dtype=jnp.int32) * 3
        st = obj_ps.add_set(st, idx, jnp.ones(n_sel, bool))
    cfg = DashConfig(k=obj_ps.kmax, n_samples=6).resolve(obj_ps.n)
    alive = jnp.ones((obj_ps.n,), bool) & ~st.sel_mask
    key = jax.random.PRNGKey(11)
    allowed = jnp.asarray(obj_ps.kmax - n_sel)
    est_ps = _estimate_elem_gains(obj_ps, st, alive, 4, allowed, key, cfg)
    est_en = _estimate_elem_gains(obj_en, st, alive, 4, allowed, key, cfg)
    np.testing.assert_allclose(np.asarray(est_en), np.asarray(est_ps),
                               rtol=1e-4, atol=1e-5)


def test_engine_estimate_at_capacity_basis():
    """With |S| = kmax nothing can be accepted: both paths must agree and
    the engine must not disturb the shared basis."""
    obj_ps = _problem(kmax=5, use_filter_engine=False)
    obj_en = _problem(kmax=5, use_filter_engine=True)
    idx = jnp.asarray([0, 4, 8, 12, 16], jnp.int32)
    st = obj_ps.add_set(obj_ps.init(), idx, jnp.ones(5, bool))
    assert int(st.count) == 5
    cfg = DashConfig(k=5, n_samples=4).resolve(obj_ps.n)
    alive = jnp.ones((obj_ps.n,), bool) & ~st.sel_mask
    key = jax.random.PRNGKey(2)
    allowed = jnp.asarray(0)
    est_ps = _estimate_elem_gains(obj_ps, st, alive, 3, allowed, key, cfg)
    est_en = _estimate_elem_gains(obj_en, st, alive, 3, allowed, key, cfg)
    np.testing.assert_allclose(np.asarray(est_en), np.asarray(est_ps),
                               rtol=1e-4, atol=1e-5)


def test_expand_basis_matches_add_set():
    """[Q | D] from expand_basis spans the same space as add_set's Q and
    yields the same residual."""
    obj = _problem()
    st = obj.add_set(obj.init(), jnp.asarray([1, 5], jnp.int32),
                     jnp.ones(2, bool))
    idx = jnp.asarray([9, 20, 33], jnp.int32)
    mask = jnp.asarray([True, True, False])
    D, resid = obj.expand_basis(st, idx, mask)
    st2 = obj.add_set(st, idx, mask)
    np.testing.assert_allclose(np.asarray(resid), np.asarray(st2.resid),
                               rtol=1e-4, atol=1e-5)
    # D columns are orthonormal and ⊥ the shared basis
    accepted = np.asarray(jnp.sum(D * D, axis=0)) > 0.5
    Dn = np.asarray(D)[:, accepted]
    np.testing.assert_allclose(Dn.T @ Dn, np.eye(Dn.shape[1]),
                               rtol=0, atol=1e-4)
    np.testing.assert_allclose(np.asarray(st.Q).T @ Dn, 0, rtol=0, atol=1e-4)


def test_dash_end_to_end_with_engine():
    """DASH runs with the engine enabled and stays within cardinality,
    deterministic given the key."""
    from repro.core import dash

    obj = _problem(use_filter_engine=True)
    cfg = DashConfig(k=obj.kmax, eps=0.25, alpha=0.6, n_samples=4)
    r1 = dash(obj, cfg, jax.random.PRNGKey(0), opt=0.9)
    r2 = dash(obj, cfg, jax.random.PRNGKey(0), opt=0.9)
    assert int(r1.sel_count) <= obj.kmax
    assert float(r1.value) == float(r2.value)
    assert bool(jnp.all(r1.sel_mask == r2.sel_mask))


# ---------------------------------------------------------------------------
# A-optimality epilogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,n,b,m", [
    (32, 64, 1, 2),
    (100, 300, 4, 5),         # n % block_n != 0 → padding
    (257, 513, 3, 8),         # everything misaligned
    (64, 1000, 2, 1),         # n_samples = 1
])
def test_aopt_filter_kernel_matches_ref(d, n, b, m):
    X, W, E, F = aopt_operands(jax.random.PRNGKey(d + n), d, n, m, b)
    got = aopt_filter_gains(X, W, E, F, 0.7, interpret=True)
    want = aopt_filter_gains_ref(X, W, E, F, 0.7)
    assert got.shape == (m, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_aopt_expand_factors_is_woodbury_inverse():
    """M_{S∪R}⁻¹ == M⁻¹ − E Eᵀ for the factors expand_factors returns."""
    obj, st = _aopt_state(n_sel=3)
    idx = jnp.asarray([7, 20, 33, 0], jnp.int32)
    mask = jnp.asarray([True, True, False, True])
    E, F = obj.expand_factors(st, idx, mask)
    st2 = obj.add_set(st, idx, mask)
    Minv = np.linalg.inv(np.asarray(st.M))
    Minv2 = np.linalg.inv(np.asarray(st2.M))
    np.testing.assert_allclose(Minv - np.asarray(E) @ np.asarray(E).T,
                               Minv2, rtol=0, atol=1e-5)
    np.testing.assert_allclose(np.asarray(F), np.asarray(E).T @ np.asarray(E),
                               rtol=0, atol=1e-6)


def _aopt_state(n_sel=0, n=50, d=24, kmax=16):
    X = RNG.normal(size=(d, n))
    X = X / np.linalg.norm(X, axis=0, keepdims=True)
    obj = AOptimalityObjective(jnp.asarray(X, jnp.float32), kmax=kmax,
                               beta2=1.0, sigma2=1.0)
    st = obj.init()
    if n_sel:
        idx = jnp.arange(n_sel, dtype=jnp.int32) * 3
        st = obj.add_set(st, idx, jnp.ones(n_sel, bool))
    return obj, st


@pytest.mark.parametrize("n_sel,m,b", [(0, 5, 4), (3, 5, 4), (3, 1, 3)])
def test_aopt_filter_batch_matches_per_sample(n_sel, m, b):
    """filter_gains_batch == vmap(gains ∘ add_set) per sample, including
    samples that duplicate already-selected stimuli."""
    obj, st = _aopt_state(n_sel)
    idx = jnp.asarray(RNG.integers(0, obj.n, size=(m, b)), jnp.int32)
    if n_sel:
        idx = idx.at[0, 0].set(0)          # duplicate of S in the sample
    mask = jnp.asarray(RNG.uniform(size=(m, b)) > 0.2)
    got = obj.filter_gains_batch(st, idx, mask)
    want = jax.vmap(lambda i, v: obj.gains(obj.add_set(st, i, v)))(idx, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_aopt_estimate_matches_per_sample_path():
    """_estimate_elem_gains via the engine == the per-sample vmap path."""
    obj, st = _aopt_state(n_sel=3)
    obj_ps = AOptimalityObjective(obj.X, kmax=obj.kmax,
                                  use_filter_engine=False)
    cfg = DashConfig(k=obj.kmax, n_samples=6).resolve(obj.n)
    alive = jnp.ones((obj.n,), bool) & ~st.sel_mask
    key = jax.random.PRNGKey(11)
    allowed = jnp.asarray(obj.kmax - 3)
    est_en = _estimate_elem_gains(obj, st, alive, 4, allowed, key, cfg)
    est_ps = _estimate_elem_gains(obj_ps, st, alive, 4, allowed, key, cfg)
    np.testing.assert_allclose(np.asarray(est_en), np.asarray(est_ps),
                               rtol=1e-5, atol=1e-6)


def test_dash_end_to_end_aopt_engine():
    from repro.core import dash, greedy

    obj, _ = _aopt_state(kmax=8)
    assert obj.use_filter_engine
    g = greedy(obj, 8)
    cfg = DashConfig(k=8, eps=0.25, alpha=0.5, n_samples=6)
    res = dash(obj, cfg, jax.random.PRNGKey(0), opt=float(g.value) * 1.05)
    assert float(res.value) >= 0.6 * float(g.value)


# ---------------------------------------------------------------------------
# logistic epilogue
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("d,n,m", [
    (32, 64, 2),
    (100, 300, 5),            # n % block_n != 0 → padding
    (257, 513, 3),            # everything misaligned
    (64, 1000, 1),            # n_samples = 1
])
def test_logistic_filter_kernel_matches_ref(d, n, m):
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    y = jnp.asarray((RNG.uniform(size=d) > 0.5), jnp.float32)
    etas = jnp.asarray(RNG.normal(size=(m, d)) * 0.4, jnp.float32)
    got = logistic_filter_gains(X, y, etas, steps=3, interpret=True)
    want = logistic_filter_gains_ref(X, y, etas, steps=3)
    assert got.shape == (m, n)
    # atol covers f32 cancellation of the O(d) log-likelihood sums on
    # near-zero gains (the padded-d summation order differs from the ref).
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def _cls_state(n_sel=0, d=60, n=30, kmax=6, **kw):
    rng = np.random.default_rng(3)
    X0 = rng.normal(size=(d, n))
    X = normalize_columns(jnp.asarray(X0, jnp.float32)) * np.sqrt(d)
    w = np.zeros(n)
    w[:4] = rng.uniform(-2, 2, 4)
    y = jnp.asarray((1 / (1 + np.exp(-X0 @ w)) > 0.5).astype(np.float32))
    obj = ClassificationObjective(X, y, kmax=kmax, **kw)
    st = obj.init()
    if n_sel:
        idx = jnp.arange(n_sel, dtype=jnp.int32) * 2
        st = obj.add_set(st, idx, jnp.ones(n_sel, bool))
    return obj, st


@pytest.mark.parametrize("n_sel,m,b", [(0, 4, 3), (2, 4, 3), (2, 1, 3)])
def test_cls_filter_batch_matches_per_sample(n_sel, m, b):
    """filter_gains_batch == vmap(gains ∘ add_set): same dedup, same
    warm start, same IRLS step count."""
    obj, st = _cls_state(n_sel)
    idx = jnp.asarray(RNG.integers(0, obj.n, size=(m, b)), jnp.int32)
    if n_sel:
        idx = idx.at[0, 0].set(0)          # duplicate of S in the sample
    mask = jnp.asarray(RNG.uniform(size=(m, b)) > 0.2)
    got = obj.filter_gains_batch(st, idx, mask)
    want = jax.vmap(lambda i, v: obj.gains(obj.add_set(st, i, v)))(idx, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_cls_filter_batch_at_capacity_edge():
    """|S| = kmax − 1: each sample may accept exactly one element, in slot
    order — the engine must reproduce add_set's capacity rule."""
    obj, st = _cls_state(n_sel=5, kmax=6)
    assert int(jnp.sum(st.sel_k)) == 5
    idx = jnp.asarray(RNG.integers(0, obj.n, size=(3, 3)), jnp.int32)
    mask = jnp.ones((3, 3), bool)
    got = obj.filter_gains_batch(st, idx, mask)
    want = jax.vmap(lambda i, v: obj.gains(obj.add_set(st, i, v)))(idx, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_cls_filter_batch_quadratic_mode():
    """gain_mode="quadratic" rides the same engine contract."""
    obj, st = _cls_state(n_sel=2, gain_mode="quadratic")
    idx = jnp.asarray(RNG.integers(0, obj.n, size=(3, 3)), jnp.int32)
    mask = jnp.ones((3, 3), bool)
    got = obj.filter_gains_batch(st, idx, mask)
    want = jax.vmap(lambda i, v: obj.gains(obj.add_set(st, i, v)))(idx, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


def test_cls_estimate_matches_per_sample_path():
    obj, st = _cls_state(n_sel=2)
    obj_ps = ClassificationObjective(obj.X, obj.y, kmax=obj.kmax,
                                     use_filter_engine=False)
    cfg = DashConfig(k=obj.kmax, n_samples=4).resolve(obj.n)
    alive = jnp.ones((obj.n,), bool) & ~st.sel_mask
    key = jax.random.PRNGKey(5)
    allowed = jnp.asarray(obj.kmax - 2)
    est_en = _estimate_elem_gains(obj, st, alive, 3, allowed, key, cfg)
    est_ps = _estimate_elem_gains(obj_ps, st, alive, 3, allowed, key, cfg)
    np.testing.assert_allclose(np.asarray(est_en), np.asarray(est_ps),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# folded guess axis (the (OPT, α) lattice through the engine)
# ---------------------------------------------------------------------------

def _guessed_regression_operands(d, k, m, b, G):
    Qs, Ds, Rs = [], [], []
    for _ in range(G):
        Q, D = _shared_and_deltas(d, k, m, b)
        Qs.append(Q)
        Ds.append(D)
        Rs.append(RNG.normal(size=(m, d)))
    return (jnp.stack(Qs), jnp.stack(Ds),
            jnp.asarray(np.stack(Rs), jnp.float32))


@pytest.mark.parametrize("d,n,k,b,m,G", [
    (100, 300, 7, 4, 5, 3),   # misaligned n AND G·m = 15 not a multiple
    (64, 128, 4, 2, 3, 1),    # G = 1 must be a no-op
    (257, 513, 5, 3, 2, 4),   # everything misaligned
])
def test_filter_gains_guess_axis_matches_per_guess(d, n, k, b, m, G):
    """One folded (G·m)-launch == G separate per-guess launches, for the
    kernel (interpret) and the lattice reference."""
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    csq = jnp.sum(X * X, axis=0)
    Q, D, R = _guessed_regression_operands(d, k, m, b, G)
    got = filter_gains(X, Q, D, R, csq, interpret=True)
    assert got.shape == (G, m, n)
    for g in range(G):
        want = filter_gains(X, Q[g], D[g], R[g], csq, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[g]), np.asarray(want))
    ref = filter_gains_ref(X, Q[0], D[0], R[0], csq)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("d,n,b,m,G", [
    (100, 300, 4, 5, 3),
    (64, 128, 2, 3, 1),       # G = 1 no-op
])
def test_aopt_guess_axis_matches_per_guess(d, n, b, m, G):
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    W = jnp.asarray(RNG.normal(size=(G, d, n)), jnp.float32)
    E = jnp.asarray(RNG.normal(size=(G, m, d, b)) * 0.3, jnp.float32)
    F = jnp.einsum("gmdb,gmdc->gmbc", E, E)
    got = aopt_filter_gains(X, W, E, F, 0.7, interpret=True)
    assert got.shape == (G, m, n)
    for g in range(G):
        want = aopt_filter_gains(X, W[g], E[g], F[g], 0.7, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[g]), np.asarray(want))


@pytest.mark.parametrize("d,n,m,G", [(100, 300, 4, 3), (64, 128, 3, 1)])
def test_logistic_guess_axis_matches_per_guess(d, n, m, G):
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    y = jnp.asarray((RNG.uniform(size=d) > 0.5), jnp.float32)
    etas = jnp.asarray(RNG.normal(size=(G, m, d)) * 0.4, jnp.float32)
    got = logistic_filter_gains(X, y, etas, steps=3, interpret=True)
    assert got.shape == (G, m, n)
    for g in range(G):
        want = logistic_filter_gains(X, y, etas[g], steps=3, interpret=True)
        np.testing.assert_array_equal(np.asarray(got[g]), np.asarray(want))


def test_vmap_over_guesses_folds_into_lattice_launch():
    """jax.vmap over the per-guess state operands (what the batched
    dash_auto lattice does) must equal the explicit folded call — the
    custom-vmap rule routes both to the same launch."""
    d, n, k, b, m, G = 48, 96, 5, 3, 4, 3
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    csq = jnp.sum(X * X, axis=0)
    Q, D, R = _guessed_regression_operands(d, k, m, b, G)
    lat = filter_gains(X, Q, D, R, csq)
    via_vmap = jax.vmap(
        lambda q, dd, rr: filter_gains(X, q, dd, rr, csq)
    )(Q, D, R)
    np.testing.assert_array_equal(np.asarray(via_vmap), np.asarray(lat))
    # under jit too (the batched lattice always runs jitted)
    via_jit = jax.jit(jax.vmap(
        lambda q, dd, rr: filter_gains(X, q, dd, rr, csq)
    ))(Q, D, R)
    np.testing.assert_allclose(np.asarray(via_jit), np.asarray(lat),
                               rtol=1e-5, atol=1e-6)


def test_vmap_with_unbatched_state_broadcasts():
    """At state0 the shared basis is a closure constant (unbatched lane):
    the custom-vmap rule must broadcast it, not crash."""
    d, n, k, b, m, G = 48, 96, 5, 3, 4, 3
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    csq = jnp.sum(X * X, axis=0)
    Q, D, R = _guessed_regression_operands(d, k, m, b, G)
    Q0 = Q[0]                                   # shared across lanes
    via_vmap = jax.vmap(
        lambda dd, rr: filter_gains(X, Q0, dd, rr, csq)
    )(D, R)
    want = jnp.stack([filter_gains(X, Q0, D[g], R[g], csq)
                      for g in range(G)])
    np.testing.assert_allclose(np.asarray(via_vmap), np.asarray(want),
                               rtol=1e-5, atol=1e-6)


def test_batched_dash_auto_equals_loop_with_engine():
    """End-to-end: the batched lattice (vmapped dash → custom-vmap →
    folded engine) reproduces loop-mode per-guess results on an
    engine-enabled objective."""
    from repro.core import dash_auto

    obj = _problem(use_filter_engine=True)
    key = jax.random.PRNGKey(1)
    kw = dict(eps=0.25, alpha=0.6, n_samples=4, n_guesses=3,
              return_lattice=True)
    _, lat_b = dash_auto(obj, obj.kmax, key, guess_mode="batched", **kw)
    _, lat_l = dash_auto(obj, obj.kmax, key, guess_mode="loop", **kw)
    np.testing.assert_array_equal(np.asarray(lat_b.value),
                                  np.asarray(lat_l.value))
    np.testing.assert_array_equal(np.asarray(lat_b.sel_mask),
                                  np.asarray(lat_l.sel_mask))


def test_dash_end_to_end_cls_engine():
    from repro.core import dash_auto, greedy

    obj, _ = _cls_state()
    assert obj.use_filter_engine
    g = greedy(obj, obj.kmax)
    res = dash_auto(obj, obj.kmax, jax.random.PRNGKey(0), eps=0.3,
                    alpha=0.4, n_samples=4, n_guesses=4)
    assert float(res.value) >= 0.4 * float(g.value)


# ---------------------------------------------------------------------------
# mixed precision: bf16 streaming with f32 accumulation, per epilogue
# ---------------------------------------------------------------------------

from repro.kernels.common import PRECISIONS, STREAM_PARITY_TOL, quantize  # noqa: E402


def _reg_operands(d=100, n=300, k=7, b=4, m=5):
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    Q, D = _shared_and_deltas(d, k, m, b)
    R = jnp.asarray(RNG.normal(size=(m, d)), jnp.float32)
    return X, Q, D, R, jnp.sum(X * X, axis=0)


def _aopt_operands(d=100, n=300, b=4, m=5):
    # Genuine Woodbury operands (W = M⁻¹X, E = P L⁻ᵀ): random W/E push
    # the epilogue's rational terms into magnitudes where the vs-f32
    # comparison measures conditioning, not bf16 quantization.
    return aopt_operands(jax.random.PRNGKey(3), d, n, m, b)


def _logistic_operands(d=100, n=300, m=5):
    # Column-normalized like the classification oracle streams it — raw
    # gaussian columns push the Newton log-likelihoods into magnitudes
    # where the vs-f32 budget is about conditioning, not quantization.
    X = jnp.asarray(RNG.normal(size=(d, n)), jnp.float32)
    X = X / jnp.linalg.norm(X, axis=0, keepdims=True)
    y = jnp.asarray((RNG.uniform(size=d) > 0.5), jnp.float32)
    etas = jnp.asarray(RNG.normal(size=(m, d)) * 0.4, jnp.float32)
    return X, y, etas


def _rel_err(a, b):
    return float(jnp.max(jnp.abs(a - b))
                 / jnp.maximum(jnp.max(jnp.abs(b)), 1e-12))


@pytest.mark.parametrize("prec", PRECISIONS)
def test_filter_gains_precision_kernel_matches_ref(prec):
    """Interpret-mode kernel == jnp ref at each precision policy: the
    ref quantizes the streamed operand exactly like the kernel's bf16
    storage + f32 upcast, so both compute the SAME function."""
    X, Q, D, R, csq = _reg_operands()
    got = filter_gains(X, Q, D, R, csq, interpret=True, precision=prec)
    want = filter_gains_ref(quantize(X, prec), Q, D, R, csq)
    tol = STREAM_PARITY_TOL[prec]["kernel_vs_ref"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("prec", PRECISIONS)
def test_aopt_filter_precision_kernel_matches_ref(prec):
    X, W, E, F = _aopt_operands()
    got = aopt_filter_gains(X, W, E, F, 0.7, interpret=True, precision=prec)
    want = aopt_filter_gains_ref(quantize(X, prec), quantize(W, prec),
                                 E, F, 0.7)
    tol = STREAM_PARITY_TOL[prec]["kernel_vs_ref"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("prec", PRECISIONS)
def test_logistic_filter_precision_kernel_matches_ref(prec):
    X, y, etas = _logistic_operands()
    got = logistic_filter_gains(X, y, etas, steps=3, interpret=True,
                                precision=prec)
    want = logistic_filter_gains_ref(quantize(X, prec), y, etas, steps=3)
    tol = STREAM_PARITY_TOL[prec]["kernel_vs_ref"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("prec", PRECISIONS)
def test_filter_precision_vs_f32_bounded(prec):
    """The precision policy's deviation from the f32 truth stays inside
    the documented per-dtype budget (docs/kernels.md), for all three
    epilogues, on BOTH routes (interpret kernel and jnp ref).  f32's
    budget is 0.0 — the policy must be the identity there."""
    tol = STREAM_PARITY_TOL[prec]["vs_f32"]
    X, Q, D, R, csq = _reg_operands()
    Xa, W, E, F = _aopt_operands()
    Xl, y, etas = _logistic_operands()
    pairs = [
        (filter_gains(X, Q, D, R, csq, interpret=True, precision=prec),
         filter_gains(X, Q, D, R, csq, interpret=True, precision="f32")),
        (filter_gains_ref(quantize(X, prec), Q, D, R, csq),
         filter_gains_ref(X, Q, D, R, csq)),
        (aopt_filter_gains(Xa, W, E, F, 0.7, interpret=True,
                           precision=prec),
         aopt_filter_gains(Xa, W, E, F, 0.7, interpret=True,
                           precision="f32")),
        (aopt_filter_gains_ref(quantize(Xa, prec), quantize(W, prec),
                               E, F, 0.7),
         aopt_filter_gains_ref(Xa, W, E, F, 0.7)),
        (logistic_filter_gains(Xl, y, etas, steps=3, interpret=True,
                               precision=prec),
         logistic_filter_gains(Xl, y, etas, steps=3, interpret=True,
                               precision="f32")),
        (logistic_filter_gains_ref(quantize(Xl, prec), y, etas, steps=3),
         logistic_filter_gains_ref(Xl, y, etas, steps=3)),
    ]
    for got, want in pairs:
        assert _rel_err(got, want) <= tol


def test_objective_precision_views_route_bf16():
    """with_precision views flip every oracle to the bf16 policy without
    mutating the parent, and the views' gains differ from f32 by at most
    the documented budget."""
    from repro.core.objectives.base import with_precision

    tol = STREAM_PARITY_TOL["bf16"]["vs_f32"]
    obj = _problem(use_filter_engine=True)
    view = with_precision(obj, "bf16")
    assert obj.precision == "f32" and view.precision == "bf16"
    assert with_precision(obj, "bf16") is view          # memoized
    assert with_precision(view, "bf16") is view         # idempotent
    st = obj.init()
    g32, g16 = obj.gains(st), view.gains(st)
    assert 0.0 < _rel_err(g16, g32) <= tol
    idx = jnp.asarray(RNG.integers(0, obj.n, size=(3, 4)), jnp.int32)
    mask = jnp.ones((3, 4), bool)
    f32b = obj.filter_gains_batch(st, idx, mask)
    f16b = view.filter_gains_batch(st, idx, mask)
    assert 0.0 < _rel_err(f16b, f32b) <= tol
