"""Selection-as-a-service smoke: offered load + injected failures.

A short serving run against the chaos lane's acceptance criterion:
offered load past the admission caps, every launch's chaos schedule
killing round 1, a deliberately tight deadline on part of the traffic —
and EVERY submitted request must end with a terminal reply (result,
labeled degraded result, or explicit rejection with a retry-after
hint), never a hang; hedged-retry DASH must commit the bitwise-
identical set an unfailed run does.  CI runs this in the distributed
job (it is device-count-agnostic); exits non-zero on any violation.

    PYTHONPATH=src python examples/serve_selection.py
"""

import numpy as np

from repro.core.objectives import normalize_columns
from repro.runtime.fault_tolerance import FailureInjector
from repro.runtime.hedging import HedgePolicy
from repro.serve import (
    FAILED,
    OK,
    REJECTED,
    AdmissionPolicy,
    LatencyModel,
    SelectRequest,
    SelectionServer,
)
from repro.utils.compile_cache import enable_compile_cache


def make_server(chaos=None):
    # Pre-seeded latency estimates: the upper tiers "cost" 100 s, so the
    # deadline-carrying slice of the traffic degrades deterministically
    # (no wall-clock races in CI).
    lm = LatencyModel()
    lm.observe("dash", 100.0)
    lm.observe("stochastic_greedy", 100.0)
    srv = SelectionServer(
        admission=AdmissionPolicy(max_batch=4, max_queue=4, max_pending=8),
        chaos=chaos, latency=lm,
        hedge=HedgePolicy(max_attempts=3, backoff_s=0.0,
                          sleep_fn=lambda s: None))
    rng = np.random.default_rng(0)
    d, n = 96, 64
    X = normalize_columns(np.asarray(rng.normal(size=(d, n)), np.float32))
    y = np.asarray(rng.normal(size=(d,)), np.float32)
    srv.register("tenant", "regression", X, y, kmax=8)
    return srv


def offered_load():
    reqs = [SelectRequest("tenant", 8, s) for s in range(12)]
    # A separate bucket (k=6) whose deadline the seeded latency model
    # says the upper tiers cannot meet → served degraded at the floor.
    reqs += [SelectRequest("tenant", 6, 100 + s, deadline_s=5.0)
             for s in range(2)]
    return reqs


def main():
    enable_compile_cache()
    baseline = make_server().serve(offered_load())

    chaotic = make_server(chaos=FailureInjector(fail_at=(1,)))
    replies = chaotic.serve(offered_load())

    assert len(replies) == len(baseline)
    dropped = [r for r in replies if r is None]
    assert not dropped, "request dropped without a reply"
    n_ok = n_rej = n_deg = n_retry = 0
    for base, rep in zip(baseline, replies):
        assert rep.status in (OK, REJECTED, FAILED), rep.status
        assert rep.status != FAILED, "hedge budget should absorb 1 failure"
        if rep.status == REJECTED:
            assert rep.retry_after_s > 0, "rejection without retry hint"
            n_rej += 1
            continue
        n_ok += 1
        if rep.degraded:
            assert rep.tier != "dash" and rep.tier is not None
            n_deg += 1
        if rep.attempts > 1:
            n_retry += 1
            # Hedged retry RESUMED: bitwise-identical to the unfailed run.
            assert base.status == OK
            np.testing.assert_array_equal(base.sel_mask, rep.sel_mask)

    assert n_retry > 0, "chaos schedule never exercised the hedge"
    assert n_deg > 0, "deadline traffic never exercised the ladder"
    print(f"serve smoke: {len(replies)} offered, {n_ok} served "
          f"({n_deg} degraded), {n_rej} shed with retry hints, "
          f"{n_retry} hedged-resume bitwise-verified — "
          "zero dropped without reply")


if __name__ == "__main__":
    main()
