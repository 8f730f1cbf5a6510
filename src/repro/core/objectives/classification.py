"""Feature selection for classification (paper §3.1, Corollary 8).

Log-likelihood objective of logistic regression:

    ℓ_class(y, w^{(S)}) = Σ_i y_i·(X_S w)_i − log(1 + e^{(X_S w)_i})

``f(S) = ℓ(w^{(S)}) − ℓ(0)`` (normalized so f(∅)=0, monotone non-negative).

Oracles
-------
* Singleton gains: per-candidate 1-D Newton refit — for every a solve
  ``max_w ℓ(η_S + x_a·w)`` with ``newton_gain_steps`` scalar-Newton
  iterations, batched over all n candidates as (d, n) elementwise work
  (``gain_mode="newton1d"``, fused on TPU by
  ``repro.kernels.logistic_gains``).  The first Newton step is exactly the
  RSC/RSM sandwich quantity ``g_a²/(2 h_a)`` of Theorem 6
  (``gain_mode="quadratic"``); further steps tighten it toward the true
  f_S(a) while staying inside the differential-submodularity sandwich.
* Set gains / solution updates do a *true refit*: ``newton_steps`` damped
  IRLS iterations on the restricted support (batched Cholesky solves).
* Filter engine (DASH's Ê_R[f_{S∪R}(a)] statistic): each perturbed state
  S ∪ R_i is fully described by its refit logits η_i, produced by a
  small per-sample IRLS refit (``expand_logits`` — identical accept rule
  and step count to ``add_set``); ``filter_gains_batch`` then runs the
  candidate Newton sweep for ALL samples in one fused launch
  (``repro.kernels.filter_gains``) instead of streaming X once per
  sample.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.objectives.base import (
    PytreeObject,
    gather_columns,
    write_accepted_column,
)
from repro.kernels.common import quantize, resolve_precision


def _sigmoid(z):
    return jax.nn.sigmoid(z)


def _loglik(eta, y):
    # Σ y·η − log(1+e^η), numerically stable via softplus.
    return jnp.sum(y * eta - jax.nn.softplus(eta))


class ClassificationState(NamedTuple):
    sel_idx: jnp.ndarray    # (kcap,) int32 — padded support indices
    sel_k: jnp.ndarray      # (kcap,) bool — which support slots are live
    w: jnp.ndarray          # (kcap,) f32 — weights on the support
    eta: jnp.ndarray        # (d,) current logits X_S w
    sel_mask: jnp.ndarray   # (n,) bool
    value: jnp.ndarray      # () f32 — ℓ(w^S) − ℓ(0)


class ClassificationDistState(NamedTuple):
    """Replicated support state for the distributed runtime.  Instead of
    global column indices (meaningless on a shard) the support stores the
    gathered COLUMNS themselves — (d, kmax) is replicated once and every
    refit is shard-independent dense math."""
    sup_cols: jnp.ndarray   # (d, kcap) support columns (zero-padded)
    sup_k: jnp.ndarray      # (kcap,) bool — live support slots
    w: jnp.ndarray          # (kcap,) f32 — weights on the support
    eta: jnp.ndarray        # (d,) current logits X_S w


class ClassificationObjective(PytreeObject):
    """ℓ_class feature selection oracle.  X: (d, n), y: (d,) ∈ {0,1}."""

    def __init__(
        self,
        X: jnp.ndarray,
        y: jnp.ndarray,
        kmax: int,
        *,
        newton_steps: int = 6,
        newton_gain_steps: int = 3,
        gain_mode: str = "newton1d",
        ridge: float = 1e-4,
        gain_eps: float = 1e-9,
        use_kernel: bool = False,
        use_filter_engine: bool = True,
        precision: str | None = None,
    ):
        self.X = jnp.asarray(X, jnp.float32)
        self.y = jnp.asarray(y, jnp.float32)
        self.d, self.n = self.X.shape
        self.kmax = int(kmax)
        self.newton_steps = int(newton_steps)
        self.newton_gain_steps = int(newton_gain_steps)
        assert gain_mode in ("newton1d", "quadratic")
        self.gain_mode = gain_mode
        self.ridge = float(ridge)
        self.gain_eps = float(gain_eps)
        self.use_kernel = bool(use_kernel)
        # Sample-batched filter engine for DASH's Ê_R[f_{S∪R}(a)] estimate
        # (repro.kernels.filter_gains); False forces the per-sample path.
        self.use_filter_engine = bool(use_filter_engine)
        # Streamed-operand policy for the newton1d kernel dispatches
        # ("f32"/"bf16" — see SupportsFilterEngine); the quadratic gain
        # mode is not kernel-backed and always runs f32.
        self.precision = resolve_precision(precision)
        self.ll0 = _loglik(jnp.zeros((self.d,)), self.y)

    def init(self) -> ClassificationState:
        return ClassificationState(
            sel_idx=jnp.zeros((self.kmax,), jnp.int32),
            sel_k=jnp.zeros((self.kmax,), bool),
            w=jnp.zeros((self.kmax,), jnp.float32),
            eta=jnp.zeros((self.d,), jnp.float32),
            sel_mask=jnp.zeros((self.n,), bool),
            value=jnp.zeros((), jnp.float32),
        )

    def value(self, state: ClassificationState):
        return state.value

    # -- oracles ----------------------------------------------------------
    def _quadratic_gains(self, eta, X=None):
        X = self.X if X is None else X             # X_local when sharded
        p = _sigmoid(eta)
        resid = self.y - p                         # (d,)
        g = X.T @ resid                            # (n,)
        wgt = p * (1.0 - p)                        # (d,)
        h = (X * X).T @ wgt                        # (n,)
        return (g * g) / (2.0 * h + self.gain_eps)

    def _gains_cols(self, eta, Xs):
        """Per-candidate Newton (or quadratic) gains at logits ``eta``
        for candidate columns ``Xs`` — the ONE gain_mode/use_kernel
        dispatch behind both the full sweep and the subset re-check."""
        if self.gain_mode == "quadratic":
            return self._quadratic_gains(eta, Xs)
        if self.use_kernel:
            from repro.kernels.logistic_gains.ops import logistic_gains

            return logistic_gains(Xs, self.y, eta,
                                  steps=self.newton_gain_steps,
                                  precision=self.precision)
        from repro.kernels.logistic_gains.ref import logistic_gains_ref

        return logistic_gains_ref(quantize(Xs, self.precision), self.y, eta,
                                  steps=self.newton_gain_steps)

    def gains(self, state: ClassificationState):
        return jnp.where(state.sel_mask, 0.0,
                         self._gains_cols(state.eta, self.X))

    def _refit(self, sup_cols, sup_mask, w0, steps):
        """Damped IRLS on a fixed padded support.  Returns (w, eta, ll)."""
        m = w0.shape[0]

        def body(_, carry):
            w, eta = carry
            p = _sigmoid(eta)
            grad = sup_cols.T @ (self.y - p) * sup_mask
            wgt = p * (1.0 - p) + 1e-6
            G = sup_cols.T @ (sup_cols * wgt[:, None])
            G = G + jnp.diag(jnp.where(sup_mask, self.ridge, 1.0))
            L = jnp.linalg.cholesky(G)
            z = jax.scipy.linalg.solve_triangular(L, grad, lower=True)
            delta = jax.scipy.linalg.solve_triangular(L.T, z, lower=False)
            delta = delta * sup_mask
            # Damped step: cap ||Δη||∞ to keep IRLS stable far from optimum.
            deta = sup_cols @ delta
            scale = jnp.minimum(1.0, 4.0 / jnp.maximum(jnp.max(jnp.abs(deta)), 1e-9))
            return w + scale * delta, eta + scale * deta

        w, eta = jax.lax.fori_loop(0, steps, body, (w0, sup_cols @ w0))
        return w, eta, _loglik(eta, self.y)

    def set_gain(self, state: ClassificationState, idx, mask):
        mcap = idx.shape[0]
        sup_idx = jnp.concatenate([state.sel_idx, idx.astype(jnp.int32)])
        # A candidate already in S must not be double-counted.
        new_mask = mask & ~state.sel_mask[idx]
        sup_mask = jnp.concatenate([state.sel_k, new_mask])
        cols = gather_columns(self.X, sup_idx, sup_mask)
        w0 = jnp.concatenate([state.w, jnp.zeros((mcap,), jnp.float32)])
        _, _, ll = self._refit(cols, sup_mask, w0, self.newton_steps)
        return jnp.maximum(ll - (state.value + self.ll0), 0.0)

    def add_set(self, state: ClassificationState, idx, mask) -> ClassificationState:
        new_mask = mask & ~state.sel_mask[idx]

        def body(j, carry):
            sel_idx, sel_k, cnt = carry
            slot = jnp.minimum(cnt, self.kmax - 1)
            take = new_mask[j] & (cnt < self.kmax)
            sel_idx = sel_idx.at[slot].set(
                jnp.where(take, idx[j].astype(jnp.int32), sel_idx[slot])
            )
            sel_k = sel_k.at[slot].set(sel_k[slot] | take)
            return sel_idx, sel_k, cnt + take.astype(jnp.int32)

        cnt0 = jnp.sum(state.sel_k.astype(jnp.int32))
        sel_idx, sel_k, _ = jax.lax.fori_loop(
            0, idx.shape[0], body, (state.sel_idx, state.sel_k, cnt0)
        )
        cols = gather_columns(self.X, sel_idx, sel_k)
        # Warm start: keep previous weights on previous slots (slots only append).
        w0 = state.w * state.sel_k
        w, eta, ll = self._refit(cols, sel_k, w0, self.newton_steps + 2)
        sel_mask = state.sel_mask.at[idx].set(state.sel_mask[idx] | mask)
        return ClassificationState(
            sel_idx=sel_idx,
            sel_k=sel_k,
            w=w,
            eta=eta,
            sel_mask=sel_mask,
            value=ll - self.ll0,
        )

    def add_one(self, state: ClassificationState, a) -> ClassificationState:
        idx = jnp.full((1,), a, jnp.int32)
        return self.add_set(state, idx, jnp.ones((1,), bool))

    def gains_subset(self, state: ClassificationState, idx):
        """Singleton gains for the candidate subset ``idx`` only — lazy
        greedy's batched re-check oracle (the per-candidate Newton sweep
        over the gathered columns instead of all of X)."""
        g = self._gains_cols(state.eta, jnp.take(self.X, idx, axis=1))
        return jnp.where(state.sel_mask[idx], 0.0, g)

    # -- sample-batched filter engine (DASH inner loop) -------------------
    def expand_logits(self, state: ClassificationState, idx, mask):
        """Refit logits η for S ∪ R without committing the state.

        Applies ``add_set``'s exact accept rule (dedup against S, then
        capacity in slot order: element j is taken iff the count after
        the earlier accepted elements is still < kmax) on the
        concatenated padded support, warm-starts from the current
        weights, and runs the same ``newton_steps + 2`` IRLS iterations.
        Returns the (d,) logits the committed state would carry.
        """
        m = idx.shape[0]
        new_mask = mask & ~state.sel_mask[idx]
        cnt0 = jnp.sum(state.sel_k.astype(jnp.int32))
        order = jnp.cumsum(new_mask.astype(jnp.int32))
        take = new_mask & (cnt0 + order <= self.kmax)
        sup_idx = jnp.concatenate([state.sel_idx, idx.astype(jnp.int32)])
        sup_mask = jnp.concatenate([state.sel_k, take])
        cols = gather_columns(self.X, sup_idx, sup_mask)
        w0 = jnp.concatenate(
            [state.w * state.sel_k, jnp.zeros((m,), jnp.float32)]
        )
        _, eta, _ = self._refit(cols, sup_mask, w0, self.newton_steps + 2)
        return eta

    def filter_gains_batch(self, state: ClassificationState, idx, mask):
        """Gains w.r.t. S ∪ R_i for every sample i in one fused pass.

        idx/mask: (n_samples, m) padded Monte-Carlo sets.  Returns the
        (n_samples, n) matrix ``jax.vmap(lambda R: gains(add_set(S, R)))``
        would produce; the per-sample work is only the small support
        refit — the candidate sweep streams X once for all samples.

        Under the batched (OPT, α) lattice this runs inside ``vmap``
        over guesses; the ``logistic_filter_gains`` wrapper's
        custom-vmap rule folds every guess's logits into ONE G·m-sample
        engine launch.
        """
        etas = jax.vmap(lambda i, v: self.expand_logits(state, i, v))(
            idx, mask
        )
        if self.gain_mode == "quadratic":
            g = jax.vmap(self._quadratic_gains)(etas)
        elif self.use_kernel:
            from repro.kernels.filter_gains.ops import logistic_filter_gains

            g = logistic_filter_gains(
                self.X, self.y, etas, steps=self.newton_gain_steps,
                precision=self.precision,
            )
        else:
            from repro.kernels.filter_gains.ref import (
                logistic_filter_gains_ref,
            )

            g = logistic_filter_gains_ref(
                quantize(self.X, self.precision), self.y, etas,
                steps=self.newton_gain_steps,
            )
        sel = jax.vmap(
            lambda i, v: state.sel_mask.at[i].set(state.sel_mask[i] | v)
        )(idx, mask)
        return jnp.where(sel, 0.0, g)

    # -- distributed contract (column-based; see DistributedObjective) ----
    def dist_init(self, X_local) -> ClassificationDistState:
        return ClassificationDistState(
            sup_cols=jnp.zeros((self.d, self.kmax), jnp.float32),
            sup_k=jnp.zeros((self.kmax,), bool),
            w=jnp.zeros((self.kmax,), jnp.float32),
            eta=jnp.zeros((self.d,), jnp.float32),
        )

    def dist_value(self, ds: ClassificationDistState):
        return _loglik(ds.eta, self.y) - self.ll0

    def dist_gains(self, ds: ClassificationDistState, X_local):
        if self.gain_mode == "quadratic":
            return self._quadratic_gains(ds.eta, X_local)
        # ops wrapper: resolve_path routes each shard to compiled Pallas
        # on TPU and the jnp reference elsewhere.
        from repro.kernels.logistic_gains.ops import logistic_gains

        return logistic_gains(X_local, self.y, ds.eta,
                              steps=self.newton_gain_steps,
                              precision=self.precision)

    def dist_set_gain(self, ds: ClassificationDistState, C, mask):
        m = C.shape[1]
        take = mask & (jnp.sum(C * C, axis=0) > 0)
        sup_cols = jnp.concatenate([ds.sup_cols, C * take[None, :]], axis=1)
        sup_mask = jnp.concatenate([ds.sup_k, take])
        w0 = jnp.concatenate([ds.w * ds.sup_k, jnp.zeros((m,), jnp.float32)])
        _, _, ll = self._refit(sup_cols, sup_mask, w0, self.newton_steps)
        return jnp.maximum(ll - _loglik(ds.eta, self.y), 0.0)

    def dist_add_set(self, ds: ClassificationDistState, C, mask, X_local):
        # Same slot-order accept rule as add_set; zero (padding) columns
        # are never accepted so they cannot burn a support slot.
        take_mask = mask & (jnp.sum(C * C, axis=0) > 0)

        def body(j, carry):
            sup_cols, sup_k, cnt = carry
            slot = jnp.minimum(cnt, self.kmax - 1)
            take = take_mask[j] & (cnt < self.kmax)
            sup_cols = write_accepted_column(sup_cols, slot, take, C[:, j])
            sup_k = sup_k.at[slot].set(sup_k[slot] | take)
            return sup_cols, sup_k, cnt + take.astype(jnp.int32)

        cnt0 = jnp.sum(ds.sup_k.astype(jnp.int32))
        sup_cols, sup_k, _ = jax.lax.fori_loop(
            0, C.shape[1], body, (ds.sup_cols, ds.sup_k, cnt0)
        )
        w, eta, _ = self._refit(sup_cols, sup_k, ds.w * ds.sup_k,
                                self.newton_steps + 2)
        return ClassificationDistState(sup_cols=sup_cols, sup_k=sup_k, w=w,
                                       eta=eta)

    def _dist_expand_logits(self, ds: ClassificationDistState, C, mask):
        """Refit logits for S ∪ R from gathered columns (accept rule and
        step count of ``dist_add_set``, without committing the state)."""
        m = C.shape[1]
        new_mask = mask & (jnp.sum(C * C, axis=0) > 0)
        cnt0 = jnp.sum(ds.sup_k.astype(jnp.int32))
        order = jnp.cumsum(new_mask.astype(jnp.int32))
        take = new_mask & (cnt0 + order <= self.kmax)
        sup_cols = jnp.concatenate([ds.sup_cols, C * take[None, :]], axis=1)
        sup_mask = jnp.concatenate([ds.sup_k, take])
        w0 = jnp.concatenate([ds.w * ds.sup_k, jnp.zeros((m,), jnp.float32)])
        _, eta, _ = self._refit(sup_cols, sup_mask, w0, self.newton_steps + 2)
        return eta

    def dist_filter_gains_batch(self, ds: ClassificationDistState, Cs, masks,
                                X_local):
        etas = jax.vmap(lambda C, v: self._dist_expand_logits(ds, C, v))(
            Cs, masks
        )
        if self.gain_mode == "quadratic":
            return jax.vmap(lambda e: self._quadratic_gains(e, X_local))(etas)
        from repro.kernels.filter_gains.ops import logistic_filter_gains

        return logistic_filter_gains(X_local, self.y, etas,
                                     steps=self.newton_gain_steps,
                                     precision=self.precision)

    # -- exact reference (tests) ------------------------------------------
    def brute_value(self, sel_idx, steps: int = 60):
        sel_idx = jnp.asarray(sel_idx, jnp.int32)
        m = sel_idx.shape[0]
        cols = self.X[:, sel_idx]
        _, _, ll = self._refit(cols, jnp.ones((m,), bool), jnp.zeros((m,)), steps)
        return ll - self.ll0
