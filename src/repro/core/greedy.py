"""SDS_MA — the greedy baseline family (Krause & Cevher [20]; paper §5).

``greedy``          — the marginal-gain greedy: k rounds, each picking
                      argmax_a f_S(a).  The gain vector is evaluated with
                      the batched oracle, which is exactly the paper's
                      "Parallel SDS_MA" (oracle queries fanned out over
                      cores ↦ one fused batched kernel / mesh shards).
``stochastic_greedy`` — Mirzasoleiman-style subsampled argmax: each round
                      restricts the argmax to a uniform sample of
                      s = ⌈(n/k)·ln(1/ε)⌉ unselected candidates — the
                      natural ε-approximate SDS_MA ((1−1/e−ε) expected
                      for submodular f) with a k·s total query cost.
``lazy_greedy``     — lazy evaluation (Minoux) with BATCHED re-checks:
                      stale upper bounds are refreshed ``batch`` at a
                      time through the objective's fused subset-gain
                      oracle (``gains_subset``).  Exact for submodular f,
                      strong heuristic otherwise — beyond-paper baseline.
``greedy_*_cost``   — adaptivity/query accounting helpers for
                      docs/algorithms.md.

Distributed twins (``greedy_distributed``, ``stochastic_greedy_distributed``)
live in ``core.distributed`` next to the sharded DASH runtime; the
``core.algorithms`` registry dispatches between the pairs.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.estimators import masked_argmax


class GreedyResult(NamedTuple):
    sel_mask: jnp.ndarray
    sel_idx: jnp.ndarray    # (k,) in pick order
    value: jnp.ndarray
    values: jnp.ndarray     # (k,) trace of f(S) after each pick
    state: Any


def greedy(obj, k: int) -> GreedyResult:
    """Parallel-oracle SDS_MA (argmax over the batched gain vector)."""

    def body(i, carry):
        state, picks, values = carry
        g = obj.gains(state)
        mask = ~state.sel_mask
        a = masked_argmax(g, mask)
        # If every gain is 0 (saturated), adding is a no-op numerically but
        # keeps shapes static; mark the pick regardless.
        state = obj.add_one(state, a)
        picks = picks.at[i].set(a)
        values = values.at[i].set(obj.value(state))
        return state, picks, values

    state0 = obj.init()
    picks0 = jnp.zeros((k,), jnp.int32)
    values0 = jnp.zeros((k,), jnp.float32)
    state, picks, values = jax.lax.fori_loop(0, k, body, (state0, picks0, values0))
    return GreedyResult(
        sel_mask=state.sel_mask,
        sel_idx=picks,
        value=obj.value(state),
        values=values,
        state=state,
    )


def subsample_size(n: int, k: int, eps: float = 0.1) -> int:
    """Mirzasoleiman et al.'s per-round sample size ⌈(n/k)·ln(1/ε)⌉,
    clipped to [1, n] — shared by both stochastic-greedy runtimes so
    their samples stay bitwise comparable."""
    s = int(math.ceil(n / max(k, 1) * math.log(1.0 / eps)))
    return max(1, min(s, n))


def stochastic_greedy(obj, k: int, key, *, subsample: int | None = None,
                      eps: float = 0.1) -> GreedyResult:
    """Subsampled-argmax SDS_MA (stochastic greedy).

    Each round draws a uniform sample of ``subsample`` (default
    ⌈(n/k)·ln(1/ε)⌉) unselected candidates via replicated Gumbel noise
    and picks the best gain inside the sample.  The gain oracle is
    evaluated for the SAMPLE ONLY (``gains_subset`` — the point of
    subsampling: k·s queries instead of greedy's k·n; objectives
    without the contract fall back to the full sweep), with the argmax
    scattered back to ground-set coordinates so ties resolve to the
    lowest global index — the same rule the distributed twin's sharded
    sweep applies.  The noise layout (one (n,) draw from
    ``fold_in(key, round)``, global top-s threshold) is shared bitwise
    with ``core.distributed.stochastic_greedy_distributed`` so the two
    runtimes select identical sets for the same key.
    """
    n = obj.n
    s = subsample_size(n, k, eps) if subsample is None else max(1, min(int(subsample), n))
    has_subset = hasattr(obj, "gains_subset")

    def body(i, carry):
        state, picks, values = carry
        noise = round_gumbel(key, i, n)
        noise = jnp.where(state.sel_mask, -jnp.inf, noise)
        nv, sidx = jax.lax.top_k(noise, s)              # the sample
        sidx = sidx.astype(jnp.int32)
        valid = jnp.isfinite(nv)                        # < s alive ⇒ pads
        g = (obj.gains_subset(state, sidx) if has_subset
             else obj.gains(state)[sidx])
        scat = jnp.full((n,), -jnp.inf).at[sidx].set(
            jnp.where(valid, g, -jnp.inf)
        )
        a = jnp.argmax(scat).astype(jnp.int32)
        state = obj.add_one(state, a)
        picks = picks.at[i].set(a)
        values = values.at[i].set(obj.value(state))
        return state, picks, values

    state0 = obj.init()
    state, picks, values = jax.lax.fori_loop(
        0, k, body,
        (state0, jnp.zeros((k,), jnp.int32), jnp.zeros((k,), jnp.float32)),
    )
    return GreedyResult(
        sel_mask=state.sel_mask,
        sel_idx=picks,
        value=obj.value(state),
        values=values,
        state=state,
    )


def round_gumbel(key, i, n: int):
    """(n,) Gumbel noise for round ``i`` of a per-pick sampler — shared
    bitwise between ``stochastic_greedy`` and its distributed twin (see
    :func:`repro.core.estimators.gumbel_noise`)."""
    from repro.core.estimators import gumbel_noise

    return gumbel_noise(jax.random.fold_in(key, i), n)


# ---------------------------------------------------------------------------
# adaptivity / oracle-query accounting (docs/algorithms.md, bench tables)
# ---------------------------------------------------------------------------

def greedy_sequential_cost(n: int, k: int) -> dict:
    """Oracle-call/adaptivity accounting for sequential SDS_MA."""
    calls = sum(n - i for i in range(k))
    return {"oracle_calls": calls, "adaptive_rounds": calls}


def greedy_parallel_cost(n: int, k: int) -> dict:
    """Parallel SDS_MA: one adaptive round per pick."""
    return {"oracle_calls": sum(n - i for i in range(k)), "adaptive_rounds": k}


def stochastic_greedy_cost(n: int, k: int, eps: float = 0.1) -> dict:
    """Stochastic greedy: one adaptive round per pick, s queries each."""
    s = subsample_size(n, k, eps)
    return {"oracle_calls": k * s, "adaptive_rounds": k}


def lazy_greedy_cost(n: int, k: int) -> dict:
    """Minoux lazy greedy: adaptivity is data-dependent — between k
    (every top bound already fresh) and the full sequential sweep; we
    report the worst case, which is what the guarantee covers."""
    calls = sum(n - i for i in range(k))
    return {"oracle_calls": calls, "adaptive_rounds": calls}


def lazy_greedy(obj, k: int, *, batch: int = 8) -> GreedyResult:
    """Minoux lazy greedy with batched re-checks (host loop).

    Exact under submodularity; for the paper's differentially submodular
    objectives it is a strong heuristic whose terminal values we report
    alongside (beyond-paper baseline).

    Re-checks are BATCHED: the ``batch`` largest stale upper bounds are
    refreshed in one fused oracle call per iteration — objectives
    exposing ``gains_subset`` (all three paper objectives + diversity)
    evaluate only those candidate columns through the same
    ``repro.kernels`` gain wrappers the full sweep uses, instead of the
    historical one-element-at-a-time ``gains(state)[a]`` host loop that
    paid a full (d, n) sweep per pop.

    ``k > n`` stops after n distinct picks (``sel_idx``/``values`` are
    then shorter than k) instead of padding the trace with duplicate
    re-commits of element 0 the way the fixed-shape ``greedy`` loop
    does.
    """
    import numpy as np

    if batch < 1:
        raise ValueError(f"batch must be >= 1, got {batch}")

    def recheck(state, idx):
        idx = jnp.asarray(idx, jnp.int32)
        if hasattr(obj, "gains_subset"):
            return np.asarray(obj.gains_subset(state, idx))
        return np.asarray(obj.gains(state))[np.asarray(idx)]

    state = obj.init()
    ub = np.array(obj.gains(state), copy=True)  # stale upper bounds
    fresh = np.zeros_like(ub, dtype=bool)
    dead = np.zeros_like(ub, dtype=bool)        # picked — never revisit
    picks, values = [], []
    for _ in range(k):
        fresh[:] = False
        while True:
            a = int(np.argmax(ub))
            if ub[a] <= 0 or fresh[a]:
                break
            # Refresh the `batch` largest stale bounds in ONE oracle call
            # (padded with `a` so the traced shape is static).  Dead
            # elements are excluded: a re-check returns gain 0 for them,
            # which would resurrect their -inf tombstone and let a
            # zero-gain endgame commit a duplicate.
            stale = np.flatnonzero(~fresh & ~dead)
            top = stale[np.argsort(-ub[stale], kind="stable")[:batch]]
            top = np.concatenate([top, np.full(batch - top.size, a)])
            g = recheck(state, top)
            ub[top] = g
            fresh[top] = True
        if not np.isfinite(ub[a]):
            break       # every element committed (k > n): stop early
        state = obj.add_one(state, a)
        ub[a] = -np.inf
        dead[a] = True
        picks.append(a)
        values.append(float(obj.value(state)))
    k_arr = jnp.asarray(picks, jnp.int32)
    return GreedyResult(
        sel_mask=state.sel_mask,
        sel_idx=k_arr,
        value=obj.value(state),
        values=jnp.asarray(values, jnp.float32),
        state=state,
    )
