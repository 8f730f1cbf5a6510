"""DASH algorithm behaviour (Alg. 1 / Thm 10) + adaptive sequencing."""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    adaptive_sequencing,
    dash,
    dash_auto,
    DashConfig,
    greedy,
    random_select,
    top_k_select,
)


class TestDash:
    def test_respects_cardinality(self, reg_obj):
        obj, k = reg_obj
        res = dash_auto(obj, k, jax.random.PRNGKey(0), n_guesses=4)
        assert int(res.sel_count) <= k
        assert int(jnp.sum(res.sel_mask)) == int(res.sel_count)

    def test_beats_random_on_planted_support(self, reg_obj):
        obj, k = reg_obj
        res = dash_auto(obj, k, jax.random.PRNGKey(0), eps=0.25,
                        alpha=0.6, n_samples=6, n_guesses=6)
        rnd = random_select(obj, k, jax.random.PRNGKey(1))
        assert float(res.value) > float(rnd.value)

    def test_competitive_with_greedy(self, reg_obj):
        """Paper §5: DASH's terminal value is comparable to SDS_MA."""
        obj, k = reg_obj
        g = greedy(obj, k)
        res = dash_auto(obj, k, jax.random.PRNGKey(0), eps=0.25,
                        alpha=0.6, n_samples=8, n_guesses=8)
        assert float(res.value) >= 0.7 * float(g.value)

    def test_exceeds_theoretical_bound(self, reg_obj):
        """f(S) ≥ (1 − 1/e^{α²} − ε)·OPT with OPT ≈ greedy value."""
        obj, k = reg_obj
        alpha, eps = 0.6, 0.25
        g = greedy(obj, k)
        res = dash_auto(obj, k, jax.random.PRNGKey(0), eps=eps, alpha=alpha,
                        n_samples=8, n_guesses=8)
        bound = (1.0 - float(np.exp(-(alpha ** 2))) - eps) * float(g.value)
        assert float(res.value) >= bound

    def test_logarithmic_rounds(self, reg_obj):
        """Adaptivity must be O(log n), far below greedy's k rounds …
        and below sequential greedy's n·k oracle rounds."""
        obj, k = reg_obj
        cfg = DashConfig(k=k, eps=0.25, alpha=0.6, n_samples=4).resolve(obj.n)
        res = dash(obj, cfg, jax.random.PRNGKey(0), opt=0.9)
        max_rounds = cfg.r * (cfg.max_filter_iters + 1)
        assert int(res.rounds) <= max_rounds

    def test_deterministic_given_key(self, reg_obj):
        obj, k = reg_obj
        cfg = DashConfig(k=k, eps=0.25, alpha=0.6, n_samples=4)
        r1 = dash(obj, cfg, jax.random.PRNGKey(7), opt=0.9)
        r2 = dash(obj, cfg, jax.random.PRNGKey(7), opt=0.9)
        assert float(r1.value) == float(r2.value)
        assert bool(jnp.all(r1.sel_mask == r2.sel_mask))

    def test_zero_opt_guess_adds_freely(self, reg_obj):
        """t = 0 ⇒ thresholds are 0 ⇒ no filtering, rounds still add."""
        obj, k = reg_obj
        cfg = DashConfig(k=k, eps=0.25, alpha=0.6, n_samples=4)
        res = dash(obj, cfg, jax.random.PRNGKey(0), opt=0.0)
        assert int(res.sel_count) > 0

    def test_trace_values_monotone(self, reg_obj):
        obj, k = reg_obj
        cfg = DashConfig(k=k, eps=0.25, alpha=0.6, n_samples=4)
        res = dash(obj, cfg, jax.random.PRNGKey(0), opt=0.9)
        vals = np.asarray(res.trace.values)
        assert np.all(np.diff(vals) >= -1e-5)

    def test_works_on_aopt(self, aopt_obj):
        obj, k = aopt_obj
        g = greedy(obj, k)
        res = dash(obj, DashConfig(k=k, eps=0.25, alpha=0.5, n_samples=6),
                   jax.random.PRNGKey(0), opt=float(g.value) * 1.05)
        assert float(res.value) >= 0.6 * float(g.value)

    def test_works_on_classification(self, cls_obj):
        obj, k = cls_obj
        g = greedy(obj, k)
        res = dash_auto(obj, k, jax.random.PRNGKey(0), eps=0.3, alpha=0.4,
                        n_samples=6, n_guesses=6)
        assert float(res.value) >= 0.4 * float(g.value)


class TestGuessLattice:
    def test_single_guess_is_geometric_midpoint(self, reg_obj):
        """n_guesses=1 must NOT degenerate to the lower endpoint g0 (the
        old ratio formula's 1/max(0, 1) exponent pinned it there)."""
        from repro.core.dash import opt_guess_lattice

        obj, k = reg_obj
        g = opt_guess_lattice(obj, 0.25, 1, k)
        g0 = float(jnp.max(obj.gains(obj.init())))
        assert g.shape == (1,)
        np.testing.assert_allclose(float(g[0]), g0 * np.sqrt(k), rtol=1e-5)

    def test_lattice_spans_feasible_range(self, reg_obj):
        from repro.core.dash import opt_guess_lattice

        obj, k = reg_obj
        g = np.asarray(opt_guess_lattice(obj, 0.25, 6, k))
        g0 = float(jnp.max(obj.gains(obj.init())))
        np.testing.assert_allclose(g[0], g0, rtol=1e-5)
        np.testing.assert_allclose(g[-1], g0 * k, rtol=1e-4)
        # geometric spacing: constant successive ratio
        ratios = g[1:] / g[:-1]
        np.testing.assert_allclose(ratios, ratios[0], rtol=1e-4)

    def test_batched_matches_loop_per_guess(self, reg_obj):
        """Each lane of the batched single-jit lattice must reproduce a
        standalone jitted ``dash`` run on that lane's key, OPT and α —
        the same selection loop, only the vmap wrapping differs.  Sets
        must match exactly; values agree to rtol 1e-6, not bitwise,
        because vmap changes the f32 fusion order (1 ulp on the CPU)."""
        from repro.core.dash import lattice_grid, opt_guess_lattice

        obj, k = reg_obj
        key = jax.random.PRNGKey(3)
        eps, alpha, n_samples, n_guesses = 0.25, 0.6, 4, 4
        best_b, lat_b = dash_auto(obj, k, key, eps=eps, alpha=alpha,
                                  n_samples=n_samples, n_guesses=n_guesses,
                                  return_lattice=True)
        cfg = DashConfig(k=k, eps=eps, alpha=alpha, n_samples=n_samples)
        opts, alphas = lattice_grid(
            opt_guess_lattice(obj, eps, n_guesses, k), [alpha])
        keys = jax.random.split(key, opts.shape[0])
        run = jax.jit(dash, static_argnums=1)
        for i in range(opts.shape[0]):
            lane = run(obj, cfg, keys[i], opts[i], alphas[i])
            np.testing.assert_array_equal(np.asarray(lat_b.sel_mask[i]),
                                          np.asarray(lane.sel_mask))
            np.testing.assert_allclose(float(lat_b.value[i]),
                                       float(lane.value), rtol=1e-6)
        assert float(best_b.value) == float(jnp.max(lat_b.value))

    def test_alpha_lattice_cross_product(self, reg_obj):
        """(OPT, α) pairs sweep jointly: n_guesses · len(alphas) runs,
        OPT-major layout, and the best still wins the argmax."""
        obj, k = reg_obj
        key = jax.random.PRNGKey(0)
        best, lat = dash_auto(obj, k, key, n_guesses=3, alphas=[0.4, 0.7],
                              n_samples=4, return_lattice=True)
        assert lat.value.shape == (6,)
        assert float(best.value) == float(jnp.max(lat.value))
        # α must actually reach the thresholds: an α=0 lane never filters
        _, lat0 = dash_auto(obj, k, key, n_guesses=1, alphas=[0.0],
                            n_samples=4, return_lattice=True)
        assert int(jnp.sum(lat0.trace.filter_iters)) == 0

    def test_nan_guess_lane_never_wins(self):
        """jnp.argmax would return a NaN lane's index; the device-side
        lattice commit must skip it (the historical host-side float
        comparison did)."""
        from repro.core.dash import DashResult, DashTrace, _best_of_lattice

        G, n, r = 3, 5, 2
        trace = DashTrace(
            values=jnp.zeros((G, r)), alive=jnp.zeros((G, r), jnp.int32),
            filter_iters=jnp.zeros((G, r), jnp.int32),
            est_set_gain=jnp.zeros((G, r)),
        )
        results = DashResult(
            sel_mask=jnp.eye(G, n, dtype=bool),
            sel_count=jnp.arange(G, dtype=jnp.int32),
            value=jnp.asarray([1.0, jnp.nan, 3.0], jnp.float32),
            rounds=jnp.arange(G, dtype=jnp.int32),
            trace=trace,
            state=None,
        )
        best = _best_of_lattice(results)
        assert float(best.value) == 3.0
        assert int(best.sel_count) == 2


class TestAdaptiveSequencing:
    def test_respects_cardinality_and_quality(self, reg_obj):
        obj, k = reg_obj
        g = greedy(obj, k)
        res = adaptive_sequencing(obj, k, jax.random.PRNGKey(0),
                                  eps=0.25, alpha=0.6,
                                  opt=float(g.value))
        assert int(res.sel_count) <= k
        assert float(res.value) > float(
            random_select(obj, k, jax.random.PRNGKey(3)).value) * 0.8


class TestBaselines:
    def test_topk_between_random_and_greedy(self, reg_obj):
        obj, k = reg_obj
        g = greedy(obj, k)
        t = top_k_select(obj, k)
        r = random_select(obj, k, jax.random.PRNGKey(0))
        assert float(t.value) <= float(g.value) + 1e-5
        assert float(t.value) >= float(r.value) * 0.8

    def test_lazy_greedy_close_to_greedy(self, reg_obj):
        from repro.core import lazy_greedy

        obj, k = reg_obj
        g = greedy(obj, k)
        lg = lazy_greedy(obj, k)
        assert float(lg.value) >= 0.9 * float(g.value)


class TestLasso:
    def test_path_hits_target_support(self, reg_problem):
        from repro.core import lasso_path_select

        X, y, k = reg_problem
        best, path = lasso_path_select(X, y, k, task="linear", iters=200)
        assert len(path) >= 1
        assert abs(int(best.nnz) - k) <= max(3, k)

    def test_logistic_path_runs(self, cls_problem):
        from repro.core import lasso_path_select

        X, y, k = cls_problem
        best, _ = lasso_path_select(X, y, k, task="logistic", iters=150)
        assert int(best.nnz) > 0


class TestSpectral:
    def test_gamma_in_unit_interval(self, reg_problem):
        from repro.core import alpha_from_gamma, gamma_regression

        X, y, k = reg_problem
        gamma = float(gamma_regression(X, k, jax.random.PRNGKey(0), 16))
        assert 0.0 <= gamma <= 1.0
        assert 0.0 <= float(alpha_from_gamma(gamma)) <= gamma + 1e-9

    def test_gamma_one_for_orthogonal(self):
        from repro.core import gamma_regression

        X = jnp.eye(32)
        gamma = float(gamma_regression(X, 4, jax.random.PRNGKey(0), 8))
        assert gamma > 0.95

    def test_aopt_gamma_formula(self, aopt_problem):
        from repro.core import gamma_aopt

        X, _ = aopt_problem
        gamma = float(gamma_aopt(X, 1.0, 1.0))
        assert 0.0 < gamma <= 1.0
