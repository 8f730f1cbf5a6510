"""Oracle correctness: incremental gains/set-gains vs brute-force refits."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import greedy


def _fd_gain(obj, base_idx, a):
    base = jnp.asarray(base_idx, jnp.int32)
    with_a = jnp.concatenate([base, jnp.asarray([a], jnp.int32)])
    return float(obj.brute_value(with_a) - obj.brute_value(base))


class TestRegression:
    def test_singleton_gains_match_bruteforce(self, reg_obj):
        obj, k = reg_obj
        st = obj.init()
        st = obj.add_one(st, 3)
        st = obj.add_one(st, 17)
        gains = obj.gains(st)
        for a in (0, 7, 25, 41):
            fd = _fd_gain(obj, [3, 17], a)
            assert abs(float(gains[a]) - fd) < 1e-4, (a, float(gains[a]), fd)

    def test_selected_gain_is_zero(self, reg_obj):
        obj, _ = reg_obj
        st = obj.add_one(obj.init(), 5)
        assert float(obj.gains(st)[5]) == 0.0

    def test_set_gain_matches_bruteforce(self, reg_obj):
        obj, _ = reg_obj
        st = obj.add_one(obj.init(), 3)
        idx = jnp.asarray([5, 9, 11], jnp.int32)
        sg = float(obj.set_gain(st, idx, jnp.ones(3, bool)))
        fd = float(obj.brute_value(jnp.asarray([3, 5, 9, 11]))
                   - obj.brute_value(jnp.asarray([3])))
        assert abs(sg - fd) < 1e-4

    def test_set_gain_respects_mask(self, reg_obj):
        obj, _ = reg_obj
        st = obj.init()
        idx = jnp.asarray([5, 9, 11], jnp.int32)
        sg_masked = float(obj.set_gain(st, idx, jnp.asarray([True, False, True])))
        sg_two = float(obj.set_gain(st, jnp.asarray([5, 11], jnp.int32),
                                    jnp.ones(2, bool)))
        assert abs(sg_masked - sg_two) < 1e-5

    def test_add_set_order_invariance(self, reg_obj):
        obj, _ = reg_obj
        idx1 = jnp.asarray([4, 9, 30], jnp.int32)
        idx2 = jnp.asarray([30, 4, 9], jnp.int32)
        v1 = float(obj.add_set(obj.init(), idx1, jnp.ones(3, bool)).value)
        v2 = float(obj.add_set(obj.init(), idx2, jnp.ones(3, bool)).value)
        assert abs(v1 - v2) < 1e-5

    def test_duplicate_add_is_noop(self, reg_obj):
        obj, _ = reg_obj
        st = obj.add_one(obj.init(), 7)
        st2 = obj.add_one(st, 7)
        assert abs(float(st.value) - float(st2.value)) < 1e-6

    def test_value_normalized(self, reg_obj):
        obj, k = reg_obj
        res = greedy(obj, k)
        assert 0.0 <= float(res.value) <= 1.0 + 1e-6

    def test_at_capacity_add_set_leaves_basis_intact(self, reg_obj):
        """Regression test: a rejected candidate (count == kmax) used to
        clobber the last basis vector with an all-zero column via the
        unguarded dynamic_update_slice."""
        obj, _ = reg_obj
        st = obj.init()
        # fill the basis to capacity
        idx = jnp.arange(obj.kmax, dtype=jnp.int32)
        st = obj.add_set(st, idx, jnp.ones(obj.kmax, bool))
        assert int(st.count) == obj.kmax
        Q0, r0, v0 = np.asarray(st.Q), np.asarray(st.resid), float(st.value)
        # further add_set calls must be exact no-ops on the basis
        for a in (obj.kmax + 1, obj.kmax + 5):
            st2 = obj.add_set(st, jnp.asarray([a], jnp.int32),
                              jnp.ones(1, bool))
            np.testing.assert_array_equal(np.asarray(st2.Q), Q0)
            np.testing.assert_array_equal(np.asarray(st2.resid), r0)
            assert int(st2.count) == obj.kmax
            assert float(st2.value) == v0
        # and gains / set_gain for already-selected elements must stay ~0
        # (with a clobbered basis the last accepted element would leave
        # span(Q) and report a spurious positive gain)
        st3 = obj.add_set(st, jnp.asarray([obj.kmax + 1], jnp.int32),
                          jnp.ones(1, bool))
        g = obj.gains(st3)
        assert bool(jnp.all(g[np.asarray(idx)] == 0.0))
        sg = float(obj.set_gain(st3, idx[-1:], jnp.ones(1, bool)))
        assert sg < 1e-4


class TestClassification:
    def test_greedy_close_to_bruteforce(self, cls_obj):
        obj, k = cls_obj
        res = greedy(obj, k)
        brute = float(obj.brute_value(np.asarray(res.sel_idx)))
        # incremental warm-start refits vs from-scratch 60-step refits
        assert abs(float(res.value) - brute) / max(brute, 1.0) < 0.05

    def test_gains_positive_and_selected_zero(self, cls_obj):
        obj, _ = cls_obj
        st = obj.add_one(obj.init(), 2)
        g = obj.gains(st)
        assert float(g[2]) == 0.0
        assert bool(jnp.all(g >= 0.0))

    def test_newton1d_gain_close_to_1d_refit(self, cls_problem):
        # first Newton step == quadratic proxy; more steps should give
        # a value >= proxy (closer to the 1-D optimum)
        from repro.core.objectives import ClassificationObjective

        X, y, k = cls_problem
        obj1 = ClassificationObjective(X, y, kmax=k, gain_mode="quadratic")
        obj3 = ClassificationObjective(X, y, kmax=k, newton_gain_steps=4)
        g1 = obj1.gains(obj1.init())
        g3 = obj3.gains(obj3.init())
        # at the top candidate the refined gain is a true ll improvement
        a = int(jnp.argmax(g3))
        fd = float(obj3.brute_value(jnp.asarray([a])))
        assert float(g3[a]) <= fd * 1.05 + 1e-3

    def test_monotone_value(self, cls_obj):
        obj, k = cls_obj
        res = greedy(obj, k)
        vals = np.asarray(res.values)
        assert np.all(np.diff(vals) >= -1e-3)


class TestAOptimality:
    def test_singleton_gains_match_bruteforce(self, aopt_obj):
        obj, _ = aopt_obj
        st = obj.add_one(obj.init(), 0)
        gains = obj.gains(st)
        for a in (5, 12, 33):
            fd = _fd_gain(obj, [0], a)
            assert abs(float(gains[a]) - fd) < 1e-4

    def test_set_gain_matches_woodbury_bruteforce(self, aopt_obj):
        obj, _ = aopt_obj
        st = obj.add_one(obj.init(), 0)
        idx = jnp.asarray([5, 9], jnp.int32)
        sg = float(obj.set_gain(st, idx, jnp.ones(2, bool)))
        fd = float(obj.brute_value(jnp.asarray([0, 5, 9]))
                   - obj.brute_value(jnp.asarray([0])))
        assert abs(sg - fd) < 1e-4

    def test_greedy_matches_bruteforce_value(self, aopt_obj):
        obj, k = aopt_obj
        res = greedy(obj, k)
        sel = np.nonzero(np.asarray(res.sel_mask))[0]
        brute = float(obj.brute_value(jnp.asarray(sel)))
        assert abs(float(res.value) - brute) < 1e-3


def _woodbury_problem(d=32, n=512):
    """Unit stimuli sharing a common direction, so M grows ill-conditioned
    as columns are added."""
    from repro.core import AOptimalityObjective

    rng = np.random.default_rng(11)
    X = rng.normal(size=(d, n)) + 1.5 * rng.normal(size=(d, 1))
    X = X / np.linalg.norm(X, axis=0, keepdims=True)
    return AOptimalityObjective(jnp.asarray(X, jnp.float32), kmax=120)


def _woodbury_rounds(n, rounds, m, seed=12):
    """(idx, mask) per round: fresh candidates, one padded slot and, from
    the second round, the previous round's first pick again."""
    order = np.random.default_rng(seed).permutation(n)
    out = []
    for r in range(rounds):
        idx = order[r * m:(r + 1) * m].copy()
        mask = np.ones(m, bool)
        if m > 1:
            mask[m - 2] = False
            if r:
                idx[1] = order[(r - 1) * m]
        out.append((jnp.asarray(idx, jnp.int32), jnp.asarray(mask)))
    return out


@pytest.mark.parametrize("path,rounds,m", [
    ("add_set", 20, 5), ("add_one", 100, 1), ("dist_add_set", 20, 5)])
def test_woodbury_refresh_keeps_shared_solve_exact(path, rounds, m):
    """After a sequence of rank-m Woodbury refreshes, the cached W equals a
    fresh M⁻¹X from the state's own factor, and its gains equal
    brute-force marginal values."""
    from repro.core.objectives.base import gather_columns

    obj = _woodbury_problem()
    st, ds = obj.init(), obj.dist_init(obj.X)
    add_set, add_one = jax.jit(obj.add_set), jax.jit(obj.add_one)
    dist_add = jax.jit(obj.dist_add_set)
    sel = np.zeros(obj.n, bool)
    for idx, mask in _woodbury_rounds(obj.n, rounds, m):
        new = np.asarray(mask) & ~sel[np.asarray(idx)]
        sel[np.asarray(idx)[new]] = True
        if path == "add_set":
            st = add_set(st, idx, mask)
        elif path == "add_one":
            st = add_one(st, idx[0])
        else:
            new = jnp.asarray(new)
            ds = dist_add(ds, gather_columns(obj.X, idx, new), new, obj.X)
    if path == "dist_add_set":
        L, W, g = ds.L, ds.W, np.asarray(obj.dist_gains(ds, obj.X))
    else:
        assert np.array_equal(np.asarray(st.sel_mask), sel)
        L, W, g = st.L, st.W, np.asarray(obj.gains(st))

    W_fresh = np.asarray(obj._minv(L, obj.X))
    gap = np.max(np.abs(np.asarray(W) - W_fresh)) / np.max(np.abs(W_fresh))
    assert gap <= 1e-5, gap
    base = np.nonzero(sel)[0]
    assert len(base) == (rounds if m == 1 else rounds * (m - 2) + 1)
    for a in np.nonzero(~sel)[0][:4]:
        assert abs(g[a] - _fd_gain(obj, base, a)) < 1e-4


def _dot_locations(text):
    """(line, location name) of each ``dot_general`` in lowered text
    printed with its debug locations."""
    names = dict(re.findall(r"^(#loc\d+) = (.*)$", text, re.M))
    out = []
    for line in text.splitlines():
        if "stablehlo.dot_general" in line:
            ref = re.search(r"loc\((#loc\d+)\)\s*$", line)
            out.append((line, names.get(ref.group(1), "") if ref else ""))
    return out


@pytest.mark.parametrize("algo", ["dash", "greedy"])
def test_aopt_state_update_lowers_without_wide_solves(algo):
    """The design lattice's and greedy's programs: no triangular solve has
    the candidate axis n in its right-hand side, and the state update's
    products that touch n run at HIGHEST precision."""
    from repro.core import select

    obj = _woodbury_problem(d=16, n=384)
    n = obj.n
    opts = dict(r=4, n_guesses=2, n_samples=4) if algo == "dash" else {}
    run = jax.jit(lambda o, key: select(algo, o, 20, key, **opts).sel_mask)
    text = run.lower(obj, jax.random.PRNGKey(0)).as_text(debug_info=True)

    wide = re.compile(rf"[<x]{n}x")
    solves = [ln for ln in text.splitlines()
              if "triangular" in ln and not ln.startswith("#loc")]
    assert solves
    assert not [ln for ln in solves if wide.search(ln)]

    # DASH's filter-engine reference carries its own products; the state
    # update is what runs under repro.add_set (greedy has no scopes).
    update = [(ln, loc) for ln, loc in _dot_locations(text)
              if wide.search(ln)
              and (algo == "greedy" or "repro.add_set" in loc)]
    assert len(update) >= 2, update
    for ln, _ in update:
        assert "precision = [HIGHEST, HIGHEST]" in ln, ln


class TestDiversity:
    def test_diversified_gains_additive(self, reg_obj):
        from repro.core import ClusterDiversity, DiversifiedObjective

        obj, _ = reg_obj
        clusters = jnp.arange(obj.n) % 5
        div = ClusterDiversity(clusters, 5, weight=0.1)
        dobj = DiversifiedObjective(obj, div)
        st = dobj.init()
        g = dobj.gains(st)
        gb = obj.gains(st)
        gd = div.gains(st.sel_mask)
        assert bool(jnp.allclose(g, gb + gd, atol=1e-6))

    def test_diversity_submodular_marginals_decrease(self):
        from repro.core import ClusterDiversity

        clusters = jnp.zeros(10, jnp.int32)
        div = ClusterDiversity(clusters, 1, weight=1.0)
        m0 = jnp.zeros(10, bool)
        m1 = m0.at[0].set(True)
        assert float(div.gains(m1)[1]) < float(div.gains(m0)[1])


class TestDistributedContract:
    """The column-based DistributedObjective methods must agree with the
    index-based single-device oracles when the whole ground set is one
    shard (X_local = X) — the sharded runner then only changes WHERE the
    math runs, not what it computes."""

    def _sets(self, n, m=4, seed=0):
        import numpy as np

        rng = np.random.default_rng(seed)
        idx = jnp.asarray(rng.choice(n, size=m, replace=False), jnp.int32)
        mask = jnp.asarray([True, True, True, False])
        return idx, mask

    def test_regression_dist_matches_index_oracles(self, reg_obj):
        import numpy as np

        from repro.core.objectives.base import gather_columns

        obj, k = reg_obj
        idx, mask = self._sets(obj.n)
        C = gather_columns(obj.X, idx, mask)

        st = obj.init()
        ds = obj.dist_init(obj.X)
        np.testing.assert_allclose(
            float(obj.dist_set_gain(ds, C, mask)),
            float(obj.set_gain(st, idx, mask)), rtol=1e-5, atol=1e-6)

        st2 = obj.add_set(st, idx, mask)
        ds2 = obj.dist_add_set(ds, C, mask, obj.X)
        np.testing.assert_allclose(float(obj.dist_value(ds2)),
                                   float(st2.value), rtol=1e-5, atol=1e-6)
        g_idx = np.asarray(obj.gains(st2))
        g_col = np.asarray(obj.dist_gains(ds2, obj.X))
        sel = np.asarray(st2.sel_mask)
        np.testing.assert_allclose(g_col[~sel], g_idx[~sel],
                                   rtol=1e-4, atol=1e-5)

        # filter-engine sweep: stacked samples, gains at S ∪ R_i
        idx2, mask2 = self._sets(obj.n, seed=1)
        Cs = jnp.stack([C, gather_columns(obj.X, idx2, mask2)])
        masks = jnp.stack([mask, mask2])
        gb = np.asarray(obj.dist_filter_gains_batch(ds, Cs, masks, obj.X))
        ref = np.asarray(obj.filter_gains_batch(
            st, jnp.stack([idx, idx2]), masks))
        for i, (ii, mm) in enumerate(((idx, mask), (idx2, mask2))):
            outside = ~np.asarray(
                st.sel_mask.at[ii].set(st.sel_mask[ii] | mm))
            np.testing.assert_allclose(gb[i][outside], ref[i][outside],
                                       rtol=1e-4, atol=1e-5)

    def test_aopt_dist_matches_index_oracles(self, aopt_obj):
        import numpy as np

        from repro.core.objectives.base import gather_columns

        obj, k = aopt_obj
        idx, mask = self._sets(obj.n, seed=2)
        C = gather_columns(obj.X, idx, mask)

        st = obj.init()
        ds = obj.dist_init(obj.X)
        np.testing.assert_allclose(
            float(obj.dist_set_gain(ds, C, mask)),
            float(obj.set_gain(st, idx, mask)), rtol=1e-5, atol=1e-6)

        st2 = obj.add_set(st, idx, mask)
        ds2 = obj.dist_add_set(ds, C, mask, obj.X)
        np.testing.assert_allclose(float(obj.dist_value(ds2)),
                                   float(st2.value), rtol=1e-5, atol=1e-6)
        g_idx = np.asarray(obj.gains(st2))
        g_col = np.asarray(obj.dist_gains(ds2, obj.X))
        sel = np.asarray(st2.sel_mask)
        np.testing.assert_allclose(g_col[~sel], g_idx[~sel],
                                   rtol=1e-4, atol=1e-5)

    def test_logistic_dist_matches_index_oracles(self, cls_obj):
        import numpy as np

        from repro.core.objectives.base import gather_columns

        obj, k = cls_obj
        idx, mask = self._sets(obj.n, seed=3)
        C = gather_columns(obj.X, idx, mask)

        st = obj.init()
        ds = obj.dist_init(obj.X)
        np.testing.assert_allclose(
            float(obj.dist_set_gain(ds, C, mask)),
            float(obj.set_gain(st, idx, mask)), rtol=1e-4, atol=1e-5)

        st2 = obj.add_set(st, idx, mask)
        ds2 = obj.dist_add_set(ds, C, mask, obj.X)
        np.testing.assert_allclose(float(obj.dist_value(ds2)),
                                   float(st2.value), rtol=1e-4, atol=1e-5)
        g_idx = np.asarray(obj.gains(st2))
        g_col = np.asarray(obj.dist_gains(ds2, obj.X))
        sel = np.asarray(st2.sel_mask)
        np.testing.assert_allclose(g_col[~sel], g_idx[~sel],
                                   rtol=1e-3, atol=1e-4)

    def test_dist_add_rejects_zero_columns(self, cls_obj):
        """Padding columns (all zeros) must not burn support slots or
        count as basis vectors in any objective's dist_add_set."""
        import numpy as np

        obj, k = cls_obj
        ds = obj.dist_init(obj.X)
        C = jnp.zeros((obj.d, 3), jnp.float32)
        ds2 = obj.dist_add_set(ds, C, jnp.ones((3,), bool), obj.X)
        assert int(jnp.sum(ds2.sup_k.astype(jnp.int32))) == 0
        np.testing.assert_array_equal(np.asarray(ds2.eta),
                                      np.asarray(ds.eta))

    def test_coreset_dist_matches_index_oracles(self, coreset_obj):
        """The fourth objective honors the full column-based contract:
        dist_* oracles == index oracles with X_local = X, including the
        fused filter-engine sweep."""
        import numpy as np

        from repro.core.objectives.base import gather_columns

        obj, k = coreset_obj
        idx, mask = self._sets(obj.n, seed=4)
        C = gather_columns(obj.X, idx, mask)

        st = obj.init()
        ds = obj.dist_init(obj.X)
        np.testing.assert_allclose(
            float(obj.dist_set_gain(ds, C, mask)),
            float(obj.set_gain(st, idx, mask)), rtol=1e-5, atol=1e-6)

        st2 = obj.add_set(st, idx, mask)
        ds2 = obj.dist_add_set(ds, C, mask, obj.X)
        np.testing.assert_allclose(float(obj.dist_value(ds2)),
                                   float(st2.value), rtol=1e-5, atol=1e-6)
        g_idx = np.asarray(obj.gains(st2))
        g_col = np.asarray(obj.dist_gains(ds2, obj.X))
        sel = np.asarray(st2.sel_mask)
        np.testing.assert_allclose(g_col[~sel], g_idx[~sel],
                                   rtol=1e-4, atol=1e-5)

        # filter-engine sweep: stacked samples, gains at S ∪ R_i
        idx2, mask2 = self._sets(obj.n, seed=5)
        Cs = jnp.stack([C, gather_columns(obj.X, idx2, mask2)])
        masks = jnp.stack([mask, mask2])
        gb = np.asarray(obj.dist_filter_gains_batch(ds, Cs, masks, obj.X))
        ref = np.asarray(obj.filter_gains_batch(
            st, jnp.stack([idx, idx2]), masks))
        for i, (ii, mm) in enumerate(((idx, mask), (idx2, mask2))):
            outside = ~np.asarray(
                st.sel_mask.at[ii].set(st.sel_mask[ii] | mm))
            np.testing.assert_allclose(gb[i][outside], ref[i][outside],
                                       rtol=1e-4, atol=1e-5)


class TestCoreset:
    """CoresetObjective feature preparation + real/padded bookkeeping
    (the A-opt oracle math itself is covered by the parent's tests and
    the contract suite above)."""

    def test_prepare_feature_columns_projects_and_normalizes(self):
        from repro.core.objectives import prepare_feature_columns

        rng = np.random.default_rng(0)
        feats = rng.normal(size=(20, 100)).astype(np.float32)
        X = prepare_feature_columns(feats, dim_cap=16,
                                    key=jax.random.PRNGKey(0))
        assert X.shape == (16, 20)
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(X), axis=0), 1.0, rtol=1e-5)
        # below the cap: no projection, just normalization
        X2 = prepare_feature_columns(feats[:, :8], dim_cap=16)
        assert X2.shape == (8, 20)

    def test_from_features_pads_to_multiple(self):
        from repro.core.objectives import CoresetObjective

        rng = np.random.default_rng(1)
        feats = rng.normal(size=(30, 12)).astype(np.float32)
        obj = CoresetObjective.from_features(feats, kmax=8, dim_cap=12,
                                             pad_multiple=8)
        assert obj.n == 32 and obj.n_real == 30
        # padded columns are zero → zero gains, never selected
        g = np.asarray(obj.gains(obj.init()))
        np.testing.assert_array_equal(g[30:], 0.0)
        res = greedy(obj, 8)
        assert not bool(jnp.any(res.sel_mask[30:]))

    def test_value_matches_brute_force(self, coreset_obj):
        obj, k = coreset_obj
        res = greedy(obj, k)
        sel = np.nonzero(np.asarray(res.sel_mask))[0]
        brute = float(obj.brute_value(jnp.asarray(sel)))
        assert abs(float(res.value) - brute) < 1e-3

    def test_feature_modes_shapes(self):
        from repro.configs import get_reduced_config
        from repro.core.objectives import coreset_features
        from repro.models import build_model

        cfg = get_reduced_config("smollm-135m")
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(2)
        batch = {"tokens": jnp.asarray(
            rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)}
        for mode in ("embed", "hidden", "grad"):
            f = coreset_features(model, params, batch, mode=mode)
            assert f.shape == (4, cfg.d_model), mode
            assert bool(jnp.all(jnp.isfinite(f))), mode
        with pytest.raises(ValueError):
            coreset_features(model, params, batch, mode="nope")
