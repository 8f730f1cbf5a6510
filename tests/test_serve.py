"""Selection-as-a-service tests (repro.serve).

The service's core contract under test: every admitted request gets
exactly one TERMINAL reply — a result, a labeled degraded result, or an
explicit rejection with a retry-after hint — never a hang; hedged
retries RESUME and commit the bitwise-identical set an unfailed run
would; warm cache updates never serve stale data and never recompile.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import (
    RegressionObjective,
    select,
    select_batched,
    stochastic_greedy,
    top_k_select,
)
from repro.runtime.fault_tolerance import FailureInjector
from repro.runtime.hedging import HedgePolicy
from repro.serve import (
    FAILED,
    OK,
    REJECTED,
    AdmissionController,
    AdmissionPolicy,
    LatencyModel,
    SelectRequest,
    SelectionServer,
    bucket_key,
    padded_batch,
)

D, N, KMAX = 60, 40, 8
NOSLEEP = HedgePolicy(max_attempts=4, backoff_s=0.0, sleep_fn=lambda s: None)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(D, N)).astype(np.float32)
    y = rng.normal(size=(D,)).astype(np.float32)
    return X, y


def make_server(data, **kw):
    srv = SelectionServer(hedge=kw.pop("hedge", NOSLEEP), **kw)
    srv.register("toy", "regression", data[0], data[1], kmax=KMAX)
    return srv


# ---------------------------------------------------------------------------
# loud validation — caller bugs raise, they don't queue
# ---------------------------------------------------------------------------

class TestValidation:
    def test_unknown_dataset(self, data):
        srv = make_server(data)
        with pytest.raises(ValueError, match="unknown dataset"):
            srv.submit(SelectRequest("nope", 4, 0))

    def test_nonpositive_k(self, data):
        srv = make_server(data)
        with pytest.raises(ValueError, match="positive"):
            srv.submit(SelectRequest("toy", 0, 0))

    def test_k_over_capacity(self, data):
        srv = make_server(data)
        with pytest.raises(ValueError, match="kmax"):
            srv.submit(SelectRequest("toy", KMAX + 1, 0))

    def test_off_ladder_algorithm(self, data):
        srv = make_server(data)
        with pytest.raises(ValueError, match="ladder"):
            srv.submit(SelectRequest("toy", 4, 0, algo="lazy_greedy"))

    def test_bad_deadline(self, data):
        srv = make_server(data)
        with pytest.raises(ValueError, match="deadline"):
            srv.submit(SelectRequest("toy", 4, 0, deadline_s=-1.0))

    def test_unknown_objective_kind(self, data):
        srv = SelectionServer()
        with pytest.raises(ValueError, match="kind"):
            srv.register("toy", "ranking", data[0], data[1], kmax=KMAX)

    def test_select_rejects_nonpositive_k(self, data):
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        with pytest.raises(ValueError, match="positive"):
            select("topk", obj, 0)
        with pytest.raises(ValueError, match="positive"):
            select("dash", obj, -3, jax.random.PRNGKey(0))

    def test_select_rejects_unknown_algo(self, data):
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        with pytest.raises(ValueError, match="unknown algorithm"):
            select("dashh", obj, 4)

    def test_select_rejects_mismatched_mesh(self, data):
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)

        class NotAMesh:
            pass

        with pytest.raises(ValueError, match="shape"):
            select("dash", obj, 4, jax.random.PRNGKey(0), mesh=NotAMesh())

    def test_select_rejects_objective_without_dist_contract(self):
        class Plain:
            pass

        with pytest.raises(ValueError, match="DistributedObjective"):
            select("dash", Plain(), 4, jax.random.PRNGKey(0), mesh=object())

    def test_select_batched_rejects_lazy_greedy(self, data):
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        with pytest.raises(ValueError, match="host-driven"):
            select_batched("lazy_greedy", obj, 4,
                           jax.random.split(jax.random.PRNGKey(0), 2))

    def test_select_batched_dash_needs_opt(self, data):
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        with pytest.raises(ValueError, match="opt"):
            select_batched("dash", obj, 4,
                           jax.random.split(jax.random.PRNGKey(0), 2))


# ---------------------------------------------------------------------------
# admission: bounded queues, bucket shapes, shedding
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_padded_batch_shapes(self):
        assert [padded_batch(b, 8) for b in (1, 2, 3, 4, 5, 8, 9, 100)] \
            == [1, 2, 4, 4, 8, 8, 8, 8]
        with pytest.raises(ValueError):
            padded_batch(0, 8)

    def test_bucket_key_separates_tenants(self):
        a = SelectRequest("fp_a", 4, 0)
        b = SelectRequest("fp_a", 5, 0)
        c = SelectRequest("fp_b", 4, 0)
        d = SelectRequest("fp_a", 4, 0, algo="topk")
        keys = {bucket_key(r) for r in (a, b, c, d)}
        assert len(keys) == 4
        assert bucket_key(a) == bucket_key(SelectRequest("fp_a", 4, 99))

    def test_queue_cap_sheds_with_retry_hint(self):
        ac = AdmissionController(AdmissionPolicy(max_queue=2, max_pending=10))
        key = ("fp", 4, "dash")
        assert ac.try_admit("r0", key) == (True, 0.0)
        assert ac.try_admit("r1", key) == (True, 0.0)
        ok, retry = ac.try_admit("r2", key)
        assert not ok and retry > 0

    def test_global_cap_sheds(self):
        ac = AdmissionController(AdmissionPolicy(max_queue=8, max_pending=2))
        assert ac.try_admit("a", ("fp", 4, "dash"))[0]
        assert ac.try_admit("b", ("fp", 5, "dash"))[0]
        ok, retry = ac.try_admit("c", ("fp", 6, "dash"))
        assert not ok and retry > 0

    def test_fifo_batches_respect_max_batch(self):
        ac = AdmissionController(AdmissionPolicy(max_batch=2, max_queue=8,
                                                 max_pending=16))
        key = ("fp", 4, "dash")
        for i in range(5):
            ac.try_admit(i, key)
        popped = []
        while (nb := ac.next_batch()) is not None:
            popped.append(nb[1])
        assert popped == [[0, 1], [2, 3], [4]]
        assert ac.pending() == 0


# ---------------------------------------------------------------------------
# end-to-end serving
# ---------------------------------------------------------------------------

class TestServe:
    def test_batch_serves_all_in_one_launch(self, data):
        srv = make_server(data)
        replies = srv.serve([SelectRequest("toy", 6, s) for s in range(5)])
        assert all(r.status == OK and r.tier == "dash" for r in replies)
        assert all(r.sel_count == 6 for r in replies)
        assert srv.stats["launches"] == 1

    def test_reply_matches_library_dash(self, data):
        """A served request commits exactly what a direct library call
        with the same (key, OPT, α, cfg) commits."""
        srv = make_server(data)
        r = srv.serve([SelectRequest("toy", 6, 2)])[0]
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        opt = srv.cache.get("toy").opt_probe[6] * srv.policy.opt_margin
        ref = select("dash", obj, 6, jax.random.PRNGKey(2), opt=opt,
                     eps=srv.policy.eps, alpha=srv.policy.alpha,
                     n_samples=srv.policy.n_samples)
        np.testing.assert_array_equal(r.sel_mask, np.asarray(ref.sel_mask))

    def test_padding_never_changes_selected_sets(self, data):
        """3 requests pad to 4 lanes; each must commit the same set it
        gets when served alone (1 lane).  Pad lanes are inert."""
        together = make_server(data).serve(
            [SelectRequest("toy", 6, s) for s in range(3)])
        for s in range(3):
            alone = make_server(data).serve([SelectRequest("toy", 6, s)])[0]
            np.testing.assert_array_equal(together[s].sel_mask,
                                          alone.sel_mask)

    def test_distinct_k_form_distinct_buckets(self, data):
        srv = make_server(data)
        replies = srv.serve([SelectRequest("toy", 4, 0),
                             SelectRequest("toy", 6, 0)])
        assert [r.sel_count for r in replies] == [4, 6]
        assert srv.stats["launches"] == 2

    def test_stochastic_greedy_tier_matches_library(self, data):
        srv = make_server(data)
        r = srv.serve([SelectRequest("toy", 5, 7, algo="stochastic_greedy")])[0]
        assert r.tier == "stochastic_greedy" and not r.degraded
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        ref = stochastic_greedy(obj, 5, jax.random.PRNGKey(7))
        np.testing.assert_array_equal(r.sel_mask, np.asarray(ref.sel_mask))

    def test_fast_tier_matches_library(self, data):
        """A ladder that lists fast serves it as a keyed single-shot
        tier: each lane commits what a direct select("fast") commits."""
        from repro.serve.degradation import DegradationLadder

        srv = make_server(data, ladder=DegradationLadder(
            ("dash", "fast", "stochastic_greedy", "topk")))
        replies = srv.serve([SelectRequest("toy", 5, s, algo="fast")
                             for s in (3, 4)])
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        for s, r in zip((3, 4), replies):
            assert r.status == OK and r.tier == "fast" and not r.degraded
            ref = select("fast", obj, 5, jax.random.PRNGKey(s))
            np.testing.assert_array_equal(r.sel_mask,
                                          np.asarray(ref.sel_mask))
            assert r.sel_count == int(ref.sel_count)

    def test_topk_tier_broadcasts_deterministic_set(self, data):
        srv = make_server(data)
        replies = srv.serve(
            [SelectRequest("toy", 5, s, algo="topk") for s in range(3)])
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        ref = np.asarray(top_k_select(obj, 5).sel_mask)
        for r in replies:
            np.testing.assert_array_equal(r.sel_mask, ref)

    def test_overload_every_request_gets_terminal_reply(self, data):
        srv = make_server(
            data, admission=AdmissionPolicy(max_batch=2, max_queue=2,
                                            max_pending=2))
        replies = srv.serve([SelectRequest("toy", 6, s) for s in range(7)])
        assert len(replies) == 7
        served = [r for r in replies if r.status == OK]
        shed = [r for r in replies if r.status == REJECTED]
        assert len(served) == 2 and len(shed) == 5
        assert all(r.retry_after_s > 0 for r in shed)

    def test_degradation_is_labeled(self, data):
        lm = LatencyModel()
        lm.observe("dash", 50.0)
        lm.observe("stochastic_greedy", 50.0)
        lm.observe("topk", 1e-4)
        srv = make_server(data, latency=lm)
        r = srv.serve([SelectRequest("toy", 6, 0, deadline_s=0.5)])[0]
        assert r.status == OK and r.tier == "topk" and r.degraded
        assert srv.stats["degraded"] == 1

    def test_deadline_exhausted_in_queue_rejects(self, data):
        t = [0.0]
        srv = make_server(data, clock=lambda: t[0])
        rid = srv.submit(SelectRequest("toy", 6, 0, deadline_s=1.0))
        t[0] = 5.0
        srv.drain()
        r = srv.reply(rid)
        assert r.status == REJECTED and r.retry_after_s > 0
        assert "queued" in r.detail

    def test_drain_timeout_rejects_leftovers(self, data):
        """The drain loop is deadline-bounded like train.serve.generate:
        whatever it cannot launch in budget is rejected, not left in
        limbo."""
        t = [0.0]

        def clock():
            t[0] += 2.0
            return t[0]

        srv = make_server(
            data, clock=clock,
            admission=AdmissionPolicy(max_batch=1, max_queue=8,
                                      max_pending=8))
        ids = [srv.submit(SelectRequest("toy", 6, s)) for s in range(4)]
        srv.drain(timeout_s=1.0)   # expires before the 2nd loop check
        replies = [srv.reply(i) for i in ids]
        assert all(r is not None for r in replies)
        shed = [r for r in replies if r.status == REJECTED]
        assert shed and all(r.retry_after_s > 0 for r in shed)
        assert all("drain deadline" in r.detail for r in shed)


# ---------------------------------------------------------------------------
# chaos mode: hedged resume, exhaustion, never-hang
# ---------------------------------------------------------------------------

class TestChaos:
    def test_hedged_retry_resumes_bitwise_identical(self, data):
        base = make_server(data).serve(
            [SelectRequest("toy", 6, s) for s in range(3)])
        srv = make_server(data, chaos=FailureInjector(fail_at=(1, 3)))
        replies = srv.serve([SelectRequest("toy", 6, s) for s in range(3)])
        for b, r in zip(base, replies):
            assert r.status == OK and r.attempts == 3
            np.testing.assert_array_equal(b.sel_mask, r.sel_mask)
        assert srv.stats["hedge_retries"] == 2

    def test_hedge_exhaustion_is_terminal_failed(self, data):
        srv = make_server(
            data,
            chaos=FailureInjector(fail_at=tuple(range(16))),
            hedge=HedgePolicy(max_attempts=2, backoff_s=0.0,
                              sleep_fn=lambda s: None))
        r = srv.serve([SelectRequest("toy", 6, 0)])[0]
        assert r.status == FAILED and "2 attempts" in r.detail

    def test_chaos_launches_use_independent_schedules(self, data):
        """Two buckets each see the full injection schedule (per-launch
        fork) — a shared injector would let the first launch consume the
        failure and shield the second."""
        srv = make_server(data, chaos=FailureInjector(fail_at=(0,)))
        replies = srv.serve([SelectRequest("toy", 4, 0),
                             SelectRequest("toy", 6, 0)])
        assert all(r.status == OK and r.attempts == 2 for r in replies)

    def test_no_request_dropped_without_reply_under_chaos(self, data):
        srv = make_server(
            data, chaos=FailureInjector(fail_at=(0, 2)),
            admission=AdmissionPolicy(max_batch=2, max_queue=2,
                                      max_pending=4))
        n = 8
        ids = [srv.submit(SelectRequest("toy", 6, s)) for s in range(n)]
        srv.drain()
        replies = [srv.reply(i) for i in ids]
        assert all(r is not None for r in replies)
        assert all(r.status in (OK, REJECTED, FAILED) for r in replies)
        assert (srv.stats["served"] + srv.stats["rejected"]
                + srv.stats["failed"]) == n


# ---------------------------------------------------------------------------
# objective cache: fingerprints, warm updates, no stale constants
# ---------------------------------------------------------------------------

class TestObjectiveCache:
    def test_same_data_shares_entry(self, data):
        srv = make_server(data)
        fp2 = srv.register("alias", "regression", data[0], data[1],
                           kmax=KMAX)
        assert fp2 == srv.cache.get("toy").fingerprint
        assert srv.cache.get("alias") is srv.cache.get("toy")

    def test_warm_update_serves_fresh_data_without_recompiling(self, data):
        X, y = data
        rng = np.random.default_rng(7)
        srv = make_server(data)
        srv.serve([SelectRequest("toy", 6, 0)])
        entry = srv.cache.get("toy")
        fp0, builds0 = entry.fingerprint, entry.builds

        cols = rng.normal(size=(D, 2)).astype(np.float32)
        fp1 = srv.update_columns("toy", [3, 7], cols)
        assert fp1 != fp0
        assert entry.opt_probe == {}          # derived scalars dropped
        r_warm = srv.serve([SelectRequest("toy", 6, 0)])[0]
        # Zero new runner builds: same shapes ⇒ same compiled executables.
        assert srv.cache.get("toy").builds == builds0

        X2 = X.copy()
        X2[:, [3, 7]] = cols
        fresh = SelectionServer(hedge=NOSLEEP)
        fresh.register("toy2", "regression", X2, y, kmax=KMAX)
        r_fresh = fresh.serve([SelectRequest("toy2", 6, 0)])[0]
        np.testing.assert_array_equal(r_warm.sel_mask, r_fresh.sel_mask)
        assert r_warm.value == pytest.approx(r_fresh.value, abs=1e-6)

    def test_warm_update_shape_mismatch_is_loud(self, data):
        srv = make_server(data)
        with pytest.raises(ValueError, match="patch shape"):
            srv.update_columns("toy", [3], np.zeros((D, 2), np.float32))

    def test_lru_eviction_bounds_entries(self, data):
        X, y = data
        srv = SelectionServer(cache_capacity=2, hedge=NOSLEEP)
        for i in range(3):
            srv.register(f"d{i}", "regression", X + i, y, kmax=KMAX)
        with pytest.raises(ValueError, match="unknown dataset"):
            srv.cache.get("d0")
        srv.cache.get("d2")                   # newest entries survive


# ---------------------------------------------------------------------------
# request-batched library entry (select_batched)
# ---------------------------------------------------------------------------

class TestSelectBatched:
    def test_dash_lanes_match_sequential_calls(self, data):
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        opt = float(top_k_select(obj, 5).value) * 1.25
        out = select_batched("dash", obj, 5, keys, opt=opt, n_samples=4)
        assert out.sel_mask.shape == (3, N)
        for i in range(3):
            ref = select("dash", obj, 5, keys[i], opt=opt, n_samples=4)
            np.testing.assert_array_equal(np.asarray(out.sel_mask[i]),
                                          np.asarray(ref.sel_mask))

    def test_deterministic_algo_broadcasts(self, data):
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        out = select_batched("topk", obj, 5, keys)
        assert out.sel_mask.shape == (4, N)
        ref = np.asarray(top_k_select(obj, 5).sel_mask)
        for i in range(4):
            np.testing.assert_array_equal(np.asarray(out.sel_mask[i]), ref)

    def test_per_lane_counts(self, data):
        obj = RegressionObjective(data[0], data[1], kmax=KMAX)
        keys = jax.random.split(jax.random.PRNGKey(1), 3)
        out = select_batched("stochastic_greedy", obj, 5, keys)
        np.testing.assert_array_equal(np.asarray(out.sel_count), [5, 5, 5])


# ---------------------------------------------------------------------------
# generate() deadline (train/serve.py bugfix)
# ---------------------------------------------------------------------------

class _StubLM:
    """Duck-typed model: prefill/decode_step over a fixed vocab."""

    V = 11

    def prefill(self, params, batch):
        b = batch["tokens"].shape[0]
        logits = jnp.tile(jnp.arange(self.V, dtype=jnp.float32), (b, 1))
        return logits, {"step_offset": jnp.zeros((), jnp.int32)}

    def decode_step(self, params, cache, tokens, pos):
        b = tokens.shape[0]
        logits = jnp.tile(jnp.arange(self.V, dtype=jnp.float32), (b, 1))
        return logits, cache


class TestGenerateDeadline:
    def _generate(self, **kw):
        from repro.train.serve import generate

        batch = {"tokens": jnp.zeros((2, 3), jnp.int32)}
        return generate(_StubLM(), {}, batch, 6, **kw)

    def test_no_deadline_returns_all_steps(self):
        assert self._generate().shape == (2, 6)

    def test_deadline_bounds_decode_loop(self):
        t = [0.0]

        def clock():
            t[0] += 1.0
            return t[0]

        out = self._generate(deadline_s=2.5, clock=clock)
        # t0=1; checks at t=2,3 → second check trips: 1 decode step ran.
        assert out.shape[1] < 6 and out.shape[1] >= 1
