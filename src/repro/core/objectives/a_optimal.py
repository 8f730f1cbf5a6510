"""Bayesian A-optimal experimental design (paper §3.1, Corollary 9; App. D).

    f_A-opt(S) = Tr(Λ⁻¹) − Tr((Λ + σ⁻² X_S X_Sᵀ)⁻¹),   Λ = β² I

Oracles
-------
State carries M = Λ + σ⁻² X_S X_Sᵀ, its Cholesky factor L, and the
cached shared solve W = M⁻¹X.  ``add_set`` of columns C refreshes W by
the rank-m Woodbury update of M' = M + σ⁻²CCᵀ,

      W' = M'⁻¹X = W − E (EᵀX),   M'⁻¹ = M⁻¹ − EEᵀ,

with E (d, m) built from P = M⁻¹C (one (d, d)·(d, m) solve against the
old factor), so no oracle ever pays a (d, d)·(d, n) triangular solve.
Every product of the update runs at HIGHEST precision: W stays M⁻¹X to
f32 for the M the state carries, round after round.

* Singleton gains (Sherman–Morrison):
      f_S(a) = σ⁻² ‖M⁻¹ x_a‖² / (1 + σ⁻² x_aᵀ M⁻¹ x_a)
  Batched: W = M⁻¹X is the cached solve; the fused column-norm/ratio
  math is ``repro.kernels.aopt_gains``.
* Set gains (Woodbury):
      f_S(R) = σ⁻² Tr( (I + σ⁻² CᵀM⁻¹C)⁻¹ · (M⁻¹C)ᵀ(M⁻¹C) ),  C = X_R.
* Filter engine (DASH's Ê_R[f_{S∪R}(a)] statistic): the perturbed
  precision M_i = M + σ⁻² C_i C_iᵀ splits as M_i⁻¹ = M⁻¹ − E_i E_iᵀ
  (``expand_factors``), so ``filter_gains_batch`` evaluates all
  ``n_samples`` perturbed states against the SHARED solve W = M⁻¹X in
  one fused pass (``repro.kernels.filter_gains``) instead of writing a
  refreshed (d, n) W per sample.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.objectives.base import PytreeObject, gather_columns
from repro.kernels.common import quantize, resolve_precision


class AOptState(NamedTuple):
    M: jnp.ndarray          # (d, d) posterior precision
    L: jnp.ndarray          # (d, d) chol(M)
    W: jnp.ndarray          # (d, n) shared solve M⁻¹X, Woodbury-refreshed
    sel_mask: jnp.ndarray   # (n,) bool
    value: jnp.ndarray      # () f32


class AOptDistState(NamedTuple):
    """Replicated precision/factor state for the distributed runtime.
    ``W`` is the shard-LOCAL shared solve M⁻¹X_local — the only (n,)-
    shaped member, Woodbury-refreshed by ``dist_add_set`` like the
    single-device cache (E comes from replicated C and L, so EᵀX_local
    needs no collective)."""
    M: jnp.ndarray          # (d, d) — replicated
    L: jnp.ndarray          # (d, d) — replicated
    W: jnp.ndarray          # (d, n_local) — shard-local


class AOptimalityObjective(PytreeObject):
    """Bayesian A-optimality oracle.  X: (d, n) stimuli columns."""

    def __init__(
        self,
        X: jnp.ndarray,
        kmax: int,
        *,
        beta2: float = 1.0,
        sigma2: float = 1.0,
        use_kernel: bool = False,
        use_filter_engine: bool = True,
        precision: str | None = None,
    ):
        self.X = jnp.asarray(X, jnp.float32)
        self.d, self.n = self.X.shape
        self.kmax = int(kmax)
        self.beta2 = float(beta2)
        self.isig2 = 1.0 / float(sigma2)
        self.use_kernel = bool(use_kernel)
        # Sample-batched filter engine for DASH's Ê_R[f_{S∪R}(a)] estimate
        # (repro.kernels.filter_gains); False forces the per-sample path.
        self.use_filter_engine = bool(use_filter_engine)
        # Streamed-operand policy for every kernel dispatch ("f32"/"bf16"
        # — see SupportsFilterEngine); the ref branches quantize to match.
        self.precision = resolve_precision(precision)
        self.tr_prior = self.d / self.beta2  # Tr(Λ⁻¹)

    def _chol(self, M):
        return jnp.linalg.cholesky(M)

    def _trace_inv(self, L):
        # Tr(M⁻¹) = ‖L⁻¹‖_F²  via triangular solve against I.
        Z = jax.scipy.linalg.solve_triangular(L, jnp.eye(self.d), lower=True)
        return jnp.sum(Z * Z)

    def init(self) -> AOptState:
        M = self.beta2 * jnp.eye(self.d)
        L = jnp.sqrt(self.beta2) * jnp.eye(self.d)
        return AOptState(
            M=M,
            L=L,
            W=self.X / self.beta2,
            sel_mask=jnp.zeros((self.n,), bool),
            value=jnp.zeros((), jnp.float32),
        )

    def value(self, state: AOptState):
        return state.value

    # -- oracles ----------------------------------------------------------
    def _minv(self, L, B):
        z = jax.scipy.linalg.solve_triangular(L, B, lower=True)
        return jax.scipy.linalg.solve_triangular(L.T, z, lower=False)

    def _gains_cols(self, Xs, Ws):
        """Sherman–Morrison gains for candidate columns ``Xs`` with their
        shared-solve slabs ``Ws`` — the ONE use_kernel/ref dispatch
        behind both the full sweep and the subset re-check."""
        if self.use_kernel:
            from repro.kernels.aopt_gains.ops import aopt_gains

            return aopt_gains(Xs, Ws, self.isig2, precision=self.precision)
        from repro.kernels.aopt_gains.ref import aopt_gains_ref

        return aopt_gains_ref(quantize(Xs, self.precision),
                              quantize(Ws, self.precision), self.isig2)

    def gains(self, state: AOptState):
        # state.W is the cached shared solve M⁻¹X
        g = self._gains_cols(self.X, state.W)
        return jnp.where(state.sel_mask, 0.0, g)

    def _set_gain_cols(self, L, C, mask):
        """Woodbury set gain from gathered columns — the ONE
        implementation behind both ``set_gain`` and ``dist_set_gain``."""
        m = C.shape[1]
        W = self._minv(L, C)                       # (d, m)
        K = jnp.eye(m) + self.isig2 * (C.T @ W)
        K = K + jnp.diag(jnp.where(mask, 0.0, 1.0))  # pin padded slots
        Lk = jnp.linalg.cholesky(K)
        Z = jax.scipy.linalg.solve_triangular(Lk, W.T, lower=True)  # (m, d)
        return self.isig2 * jnp.sum(Z * Z)

    def set_gain(self, state: AOptState, idx, mask):
        C = gather_columns(self.X, idx, mask)      # (d, m)
        return self._set_gain_cols(state.L, C, mask)

    def add_set(self, state: AOptState, idx, mask) -> AOptState:
        # Re-adding an already-selected stimulus must be a no-op for set
        # semantics, so mask out duplicates.
        new_mask = mask & ~state.sel_mask[idx]
        C = gather_columns(self.X, idx, new_mask)
        M, L, W = self._update(state.M, state.L, state.W, C, self.X)
        sel = state.sel_mask.at[idx].set(state.sel_mask[idx] | mask)
        value = self.tr_prior - self._trace_inv(L)
        return AOptState(M=M, L=L, W=W, sel_mask=sel, value=value)

    def _update(self, M, L, W, C, X):
        """(M', L', W') for M' = M + σ⁻²CCᵀ — the ONE state update behind
        ``add_set`` and ``dist_add_set``.  C must be exactly the columns
        that enter M (padded and duplicate slots zeroed).

        W' = W − E (EᵀX) with E from P = M⁻¹C against the OLD factor L,
        so each update adds one rounding and none compounds.  EᵀX reads
        X, not W: under ``vmap`` over states X is unbatched and is read
        once for all of them.  The products are HIGHEST because a
        default-precision f32 product on a TPU is one bf16 pass."""
        hi = jax.lax.Precision.HIGHEST
        M = M + self.isig2 * jnp.matmul(C, C.T, precision=hi)
        E, _ = self._woodbury_factors(C, self._minv(L, C))
        G = jnp.matmul(E.T, X, precision=hi)                   # (m, n)
        W = W - jnp.matmul(E, G, precision=hi)
        return M, self._chol(M), W

    def add_one(self, state: AOptState, a) -> AOptState:
        idx = jnp.full((1,), a, jnp.int32)
        return self.add_set(state, idx, jnp.ones((1,), bool))

    def gains_subset(self, state: AOptState, idx):
        """Singleton gains for the candidate subset ``idx`` only — lazy
        greedy's batched re-check oracle.  The cached shared solve W
        makes this a pure column gather + the fused ratio math."""
        g = self._gains_cols(jnp.take(self.X, idx, axis=1),
                             jnp.take(state.W, idx, axis=1))
        return jnp.where(state.sel_mask[idx], 0.0, g)

    # -- sample-batched filter engine (DASH inner loop) -------------------
    def expand_factors(self, state: AOptState, idx, mask, W=None):
        """Woodbury factors of the perturbed precision for S ∪ R.

        With C = X_R (duplicates of S masked out, matching ``add_set``
        semantics) and K = I + σ⁻² CᵀM⁻¹C = L_K L_Kᵀ:

            M_{S∪R}⁻¹ = M⁻¹ − E Eᵀ,   E = σ⁻¹ (M⁻¹C) L_K⁻ᵀ   (d, m)

        so the filter engine can evaluate every perturbed state against
        the shared solve W = M⁻¹X.  When that shared solve is already
        available (``filter_gains_batch`` computes it once for all
        samples) pass it as ``W``: M⁻¹C is then just a column gather of
        W instead of a fresh pair of (d, d) triangular solves per
        sample.  Returns (E, F) with F = EᵀE — padded/duplicate slots
        produce zero columns of E and contribute nothing.
        """
        new_mask = mask & ~state.sel_mask[idx]
        C = gather_columns(self.X, idx, new_mask)      # (d, m)
        if W is None:
            P = self._minv(state.L, C)                 # (d, m) = M⁻¹C
        else:
            P = gather_columns(W, idx, new_mask)
        return self._woodbury_factors(C, P)

    def filter_gains_batch(self, state: AOptState, idx, mask):
        """Gains w.r.t. S ∪ R_i for every sample i in one fused pass.

        idx/mask: (n_samples, m) padded Monte-Carlo sets.  Returns the
        (n_samples, n) matrix ``jax.vmap(lambda R: gains(add_set(S, R)))``
        would produce, without re-factorizing M per sample.

        Under the batched (OPT, α) lattice this runs inside ``vmap``
        over guesses; the ``aopt_filter_gains`` wrapper's custom-vmap
        rule folds every guess's (W, E, F) into ONE guess-axis engine
        launch (X streamed once, each guess's W slab fetched at its
        guess boundary).
        """
        W = state.W                                    # (d, n) — shared
        E, F = jax.vmap(lambda i, v: self.expand_factors(state, i, v, W))(
            idx, mask
        )
        if self.use_kernel:
            from repro.kernels.filter_gains.ops import aopt_filter_gains

            g = aopt_filter_gains(self.X, W, E, F, self.isig2,
                                  precision=self.precision)
        else:
            from repro.kernels.filter_gains.ref import aopt_filter_gains_ref

            g = aopt_filter_gains_ref(quantize(self.X, self.precision),
                                      quantize(W, self.precision), E, F,
                                      self.isig2)
        sel = jax.vmap(
            lambda i, v: state.sel_mask.at[i].set(state.sel_mask[i] | v)
        )(idx, mask)
        return jnp.where(sel, 0.0, g)

    def _woodbury_factors(self, C, P):
        """(E, F) of M + σ⁻²CCᵀ given C and P = M⁻¹C — the ONE
        implementation behind ``expand_factors`` (index-based, with the
        shared-solve gather), ``dist_filter_gains_batch`` and the state
        update ``_update``."""
        m = C.shape[1]
        K = jnp.eye(m) + self.isig2 * jnp.matmul(
            C.T, P, precision=jax.lax.Precision.HIGHEST)
        Lk = jnp.linalg.cholesky(K)
        Et = jnp.sqrt(self.isig2) * jax.scipy.linalg.solve_triangular(
            Lk, P.T, lower=True
        )                                              # (m, d) = Eᵀ
        return Et.T, Et @ Et.T

    # -- distributed contract (column-based; see DistributedObjective) ----
    def dist_init(self, X_local) -> AOptDistState:
        return AOptDistState(
            M=self.beta2 * jnp.eye(self.d),
            L=jnp.sqrt(self.beta2) * jnp.eye(self.d),
            W=X_local / self.beta2,
        )

    def dist_value(self, ds: AOptDistState):
        return self.tr_prior - self._trace_inv(ds.L)

    def dist_gains(self, ds: AOptDistState, X_local):
        # ops wrapper: resolve_path routes each shard to compiled Pallas
        # on TPU and the jnp reference elsewhere.
        from repro.kernels.aopt_gains.ops import aopt_gains

        return aopt_gains(X_local, ds.W, self.isig2,
                          precision=self.precision)

    def dist_set_gain(self, ds: AOptDistState, C, mask):
        return self._set_gain_cols(ds.L, C, mask)

    def dist_add_set(self, ds: AOptDistState, C, mask, X_local):
        C = C * mask.astype(C.dtype)[None, :]
        return AOptDistState(*self._update(ds.M, ds.L, ds.W, C, X_local))

    def dist_filter_gains_batch(self, ds: AOptDistState, Cs, masks, X_local):
        Cs = Cs * masks.astype(Cs.dtype)[:, None, :]
        E, F = jax.vmap(
            lambda C: self._woodbury_factors(C, self._minv(ds.L, C))
        )(Cs)
        from repro.kernels.filter_gains.ops import aopt_filter_gains

        return aopt_filter_gains(X_local, ds.W, E, F, self.isig2,
                                 precision=self.precision)

    # -- exact reference (tests) ------------------------------------------
    def brute_value(self, sel_idx):
        Xs = self.X[:, jnp.asarray(sel_idx)]
        M = self.beta2 * jnp.eye(self.d) + self.isig2 * (Xs @ Xs.T)
        return self.tr_prior - jnp.trace(jnp.linalg.inv(M))
