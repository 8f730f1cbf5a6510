"""Diversity-promoting submodular regularizers (paper Cor. 7–9, d(S) terms).

The paper adds a monotone submodular diversity function d(S) to each
objective and shows differential submodularity is preserved.  We provide a
cluster-coverage diversity

    d(S) = w · Σ_c √|S ∩ G_c|

(concave-of-modular ⇒ monotone submodular) where G_c is a partition of the
ground set (e.g. feature clusters), plus a wrapper that augments any base
objective's oracles with the diversity marginals.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.core.objectives.base import PytreeObject


class ClusterDiversity(PytreeObject):
    """d(S) = weight · Σ_c sqrt(count_c(S)) over a ground-set partition."""

    def __init__(self, clusters: jnp.ndarray, n_clusters: int, weight: float = 1.0):
        self.clusters = jnp.asarray(clusters, jnp.int32)  # (n,) cluster ids
        self.n_clusters = int(n_clusters)
        self.weight = float(weight)

    def counts(self, sel_mask):
        return jnp.zeros((self.n_clusters,)).at[self.clusters].add(
            sel_mask.astype(jnp.float32)
        )

    def value(self, sel_mask):
        return self.weight * jnp.sum(jnp.sqrt(self.counts(sel_mask)))

    def gains(self, sel_mask):
        """Marginal d_S(a) per element (0 for already-selected)."""
        c = self.counts(sel_mask)                      # (C,)
        marg_c = jnp.sqrt(c + 1.0) - jnp.sqrt(c)       # (C,)
        g = self.weight * marg_c[self.clusters]
        return jnp.where(sel_mask, 0.0, g)

    def gains_at(self, sel_mask, idx):
        """Marginals for a candidate subset only: one counts scatter,
        then per-candidate gathers — no (n,)-wide marginal sweep."""
        c = self.counts(sel_mask)[self.clusters[idx]]  # (m,)
        g = self.weight * (jnp.sqrt(c + 1.0) - jnp.sqrt(c))
        return jnp.where(sel_mask[idx], 0.0, g)

    def set_gain(self, sel_mask, idx, mask):
        c = self.counts(sel_mask)
        add = jnp.zeros((self.n_clusters,)).at[idx].add(
            (mask & ~sel_mask[idx]).astype(jnp.float32)
        )
        return self.weight * jnp.sum(jnp.sqrt(c + add) - jnp.sqrt(c))


class DivState(NamedTuple):
    base: tuple
    # diversity value is recomputed from base.sel_mask — no extra state


class DiversityState(NamedTuple):
    sel_mask: jnp.ndarray   # (n,) bool
    value: jnp.ndarray      # () f32


class DiversityObjective(PytreeObject):
    """Pure cluster-coverage diversity as a standalone ``Objective``.

    d(S) alone is monotone SUBMODULAR (not merely differentially
    submodular), which makes this the exactness reference for lazy
    greedy: Minoux's invariant holds, so ``lazy_greedy`` must match
    ``greedy`` pick for pick.  Also a coverage workload in its own right
    (pick k maximally cluster-diverse items).
    """

    def __init__(self, clusters, n_clusters: int, *, weight: float = 1.0,
                 kmax: int | None = None):
        self.div = ClusterDiversity(clusters, n_clusters, weight)
        self.n = int(self.div.clusters.shape[0])
        self.kmax = int(kmax) if kmax is not None else self.n

    def init(self) -> DiversityState:
        return DiversityState(
            sel_mask=jnp.zeros((self.n,), bool),
            value=jnp.zeros((), jnp.float32),
        )

    def value(self, state: DiversityState):
        return state.value

    def gains(self, state: DiversityState):
        return self.div.gains(state.sel_mask)

    def gains_subset(self, state: DiversityState, idx):
        return self.div.gains_at(state.sel_mask, idx)

    def set_gain(self, state: DiversityState, idx, mask):
        return self.div.set_gain(state.sel_mask, idx, mask)

    def add_set(self, state: DiversityState, idx, mask) -> DiversityState:
        sel = state.sel_mask.at[idx].set(state.sel_mask[idx] | mask)
        return DiversityState(sel_mask=sel, value=self.div.value(sel))

    def add_one(self, state: DiversityState, a) -> DiversityState:
        idx = jnp.full((1,), a, jnp.int32)
        return self.add_set(state, idx, jnp.ones((1,), bool))


class DiversifiedObjective(PytreeObject):
    """f_div(S) = f(S) + d(S): wraps any base objective with diversity."""

    def __init__(self, base, diversity: ClusterDiversity):
        self.base = base
        self.div = diversity
        self.n = base.n
        self.kmax = base.kmax

    def init(self):
        return self.base.init()

    def value(self, state):
        return self.base.value(state) + self.div.value(state.sel_mask)

    def gains(self, state):
        return self.base.gains(state) + self.div.gains(state.sel_mask)

    def gains_subset(self, state, idx):
        if not hasattr(self.base, "gains_subset"):
            return self.gains(state)[idx]
        return self.base.gains_subset(state, idx) + self.div.gains_at(
            state.sel_mask, idx
        )

    def set_gain(self, state, idx, mask):
        return self.base.set_gain(state, idx, mask) + self.div.set_gain(
            state.sel_mask, idx, mask
        )

    def add_set(self, state, idx, mask):
        return self.base.add_set(state, idx, mask)

    def add_one(self, state, a):
        return self.base.add_one(state, a)
