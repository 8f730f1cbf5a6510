"""Feature selection for linear regression (paper §3.1, Corollary 7).

Objective (normalized to [0, 1] by ||y||²):

    f(S) = ( ||y||² − min_w ||y − X_S w||² ) / ||y||²
         = ||proj_{span(X_S)} y||² / ||y||²

which is the ℓ_reg variance-reduction utility of the paper.  The R²
goodness-of-fit variant (Appendix F) is identical after column
normalization, which ``normalize_columns`` provides.

Fast oracle
-----------
We maintain an orthonormal basis Q of span(X_S) (incremental modified
Gram–Schmidt).  With residual r = y − QQᵀy:

    f_S(a)  = (x_aᵀ r)² / (‖x_a‖² − ‖Qᵀ x_a‖²)          (singleton gains)
    f_S(R)  = bᵀ G⁻¹ b,  C̃ = (I−QQᵀ) X_R, G = C̃ᵀC̃, b = C̃ᵀ r

The batched singleton-gain evaluation — one (k×d)·(d×n) GEMM plus
elementwise math — is the per-round hot-spot that
``repro.kernels.marginal_gains`` fuses on TPU.  DASH's filter statistic
additionally batches over Monte-Carlo samples through the shared filter
engine (``repro.kernels.filter_gains``, regression epilogue): the basis
is split into the shared Q plus per-sample deltas by ``expand_basis``
and all samples ride one fused launch via ``filter_gains_batch``
(the ``SupportsFilterEngine`` contract, gated by ``use_filter_engine``).
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


class RegressionState(NamedTuple):
    Q: jnp.ndarray          # (d, kcap) orthonormal basis (zero-padded cols)
    count: jnp.ndarray      # () int32 — number of basis vectors
    resid: jnp.ndarray      # (d,) current residual y − QQᵀy
    sel_mask: jnp.ndarray   # (n,) bool
    value: jnp.ndarray      # () f32 — normalized f(S)


class RegressionDistState(NamedTuple):
    """Replicated oracle state for the distributed runtime (no sel_mask —
    the runner keeps the shard-local selection mask).  ``col_sq`` is the
    shard-LOCAL column-norm cache feeding the gain kernels."""
    Q: jnp.ndarray          # (d, kcap) orthonormal basis — replicated
    count: jnp.ndarray      # () int32 — replicated
    resid: jnp.ndarray      # (d,) — replicated
    col_sq: jnp.ndarray     # (n_local,) — shard-local


# ---------------------------------------------------------------------------
# incremental-MGS column primitives — shared by the single-device oracle,
# the filter engine AND the distributed runtime (one accept rule, one
# capacity guard; previously hand-mirrored in core/distributed.py)
# ---------------------------------------------------------------------------

def mgs_extend(Q, count, resid, C, kmax: int, span_tol: float = 1e-6):
    """Commit the columns of C into the orthonormal basis Q (in place).

    Each column is MGS-orthonormalized (two projection rounds) against
    the padded basis and appended at slot ``count``.  Rejected columns —
    zero/padded (nrm0 = 0), numerically in span, or at capacity — leave
    Q, count and resid untouched; in particular the write into the last
    slot is guarded so an at-capacity call cannot clobber the basis
    vector already stored there.  Returns ``(Q, count, resid)``.
    """
    m = C.shape[1]

    def body(j, carry):
        Q, count, resid = carry
        v = C[:, j]
        nrm0 = jnp.sqrt(jnp.sum(v * v))
        v = v - Q @ (Q.T @ v)
        v = v - Q @ (Q.T @ v)
        nrm = jnp.sqrt(jnp.sum(v * v))
        accept = (
            (nrm0 > 0)
            & (nrm > span_tol * jnp.maximum(nrm0, 1.0))
            & (count < kmax)
        )
        q = jnp.where(accept, v / jnp.maximum(nrm, 1e-30), 0.0)
        Q = write_accepted_column(Q, jnp.minimum(count, kmax - 1), accept, q)
        resid = resid - q * jnp.dot(q, resid)
        return Q, count + accept.astype(jnp.int32), resid

    return jax.lax.fori_loop(0, m, body, (Q, count, resid))


def mgs_expand(Q, count, resid, C, kmax: int, span_tol: float = 1e-6):
    """MGS deltas for S ∪ R without rewriting the shared basis.

    The filter-engine analogue of :func:`mgs_extend`: the same accept
    rule (projections run against Q *and* the earlier deltas), but
    accepted columns land in a fresh (d, m) buffer D ⊥ span(Q) so the
    engine can reuse the replicated Q across every Monte-Carlo sample.
    Returns ``(D, resid)`` — the per-sample delta basis and residual.
    """
    m = C.shape[1]

    def body(j, carry):
        D, dcount, r = carry
        v = C[:, j]
        nrm0 = jnp.sqrt(jnp.sum(v * v))
        # Two rounds of MGS against the shared basis + earlier deltas.
        v = v - Q @ (Q.T @ v)
        v = v - D @ (D.T @ v)
        v = v - Q @ (Q.T @ v)
        v = v - D @ (D.T @ v)
        nrm = jnp.sqrt(jnp.sum(v * v))
        accept = (
            (nrm0 > 0)
            & (nrm > span_tol * jnp.maximum(nrm0, 1.0))
            & (count + dcount < kmax)
        )
        q = jnp.where(accept, v / jnp.maximum(nrm, 1e-30), 0.0)
        D = write_accepted_column(D, jnp.minimum(dcount, m - 1), accept, q)
        r = r - q * jnp.dot(q, r)
        return D, dcount + accept.astype(jnp.int32), r

    D0 = jnp.zeros((Q.shape[0], m), jnp.float32)
    D, _, r = jax.lax.fori_loop(
        0, m, body, (D0, jnp.zeros((), jnp.int32), resid)
    )
    return D, r


class RegressionObjective(PytreeObject):
    """ℓ_reg feature selection oracle.  X: (d, n) columns, y: (d,)."""

    def __init__(
        self,
        X: jnp.ndarray,
        y: jnp.ndarray,
        kmax: int,
        *,
        span_tol: float = 1e-6,
        jitter: float = 1e-8,
        use_kernel: bool = False,
        use_filter_engine: bool = True,
        precision: str | None = None,
    ):
        self.X = jnp.asarray(X, jnp.float32)
        self.y = jnp.asarray(y, jnp.float32)
        self.d, self.n = self.X.shape
        self.kmax = int(kmax)
        self.span_tol = float(span_tol)
        self.jitter = float(jitter)
        self.use_kernel = bool(use_kernel)
        # Sample-batched filter engine for DASH's Ê_R[f_{S∪R}(a)] estimate
        # (repro.kernels.filter_gains); False forces the per-sample path.
        self.use_filter_engine = bool(use_filter_engine)
        # Streamed-operand policy for every kernel dispatch ("f32"/"bf16"
        # — see SupportsFilterEngine); the ref branches quantize to match.
        self.precision = resolve_precision(precision)
        self.ysq = jnp.maximum(jnp.sum(self.y * self.y), 1e-12)
        self.col_sq = jnp.sum(self.X * self.X, axis=0)  # (n,)

    # -- state ------------------------------------------------------------
    def init(self) -> RegressionState:
        return RegressionState(
            Q=jnp.zeros((self.d, self.kmax), jnp.float32),
            count=jnp.zeros((), jnp.int32),
            resid=self.y,
            sel_mask=jnp.zeros((self.n,), bool),
            value=jnp.zeros((), jnp.float32),
        )

    def value(self, state: RegressionState):
        return state.value

    # -- oracles ----------------------------------------------------------
    def _gains_cols(self, state: RegressionState, Xs, cs):
        """Normalized singleton gains for candidate columns ``Xs`` with
        squared norms ``cs`` — the ONE use_kernel/ref dispatch behind
        both the full sweep and the subset re-check."""
        if self.use_kernel:
            from repro.kernels.marginal_gains.ops import regression_gains

            g = regression_gains(Xs, state.Q, state.resid, cs,
                                 precision=self.precision)
        else:
            from repro.kernels.marginal_gains.ref import regression_gains_ref

            g = regression_gains_ref(quantize(Xs, self.precision), state.Q,
                                     state.resid, cs)
        return g / self.ysq

    def gains(self, state: RegressionState):
        g = self._gains_cols(state, self.X, self.col_sq)
        return jnp.where(state.sel_mask, 0.0, g)

    def set_gain(self, state: RegressionState, idx, mask):
        C = gather_columns(self.X, idx, mask)                  # (d, m)
        Ct = C - state.Q @ (state.Q.T @ C)                     # project ⟂ span(Q)
        m = idx.shape[0]
        G = Ct.T @ Ct
        # Padded/in-span columns: pin the diagonal so Cholesky stays PD.
        diag_fix = jnp.where(mask, self.jitter * jnp.maximum(self.col_sq[idx], 1.0), 1.0)
        G = G + jnp.diag(diag_fix)
        b = Ct.T @ state.resid * mask
        L = jnp.linalg.cholesky(G)
        z = jax.scipy.linalg.solve_triangular(L, b, lower=True)
        return jnp.sum(z * z) / self.ysq

    def add_set(self, state: RegressionState, idx, mask) -> RegressionState:
        C = gather_columns(self.X, idx, mask)                  # (d, m)
        Q, count, resid = mgs_extend(
            state.Q, state.count, state.resid, C, self.kmax, self.span_tol
        )
        sel = state.sel_mask.at[idx].set(state.sel_mask[idx] | mask)
        value = (self.ysq - jnp.sum(resid * resid)) / self.ysq
        return RegressionState(Q=Q, count=count, resid=resid, sel_mask=sel, value=value)

    def add_one(self, state: RegressionState, a) -> RegressionState:
        idx = jnp.full((1,), a, jnp.int32)
        return self.add_set(state, idx, jnp.ones((1,), bool))

    def gains_subset(self, state: RegressionState, idx):
        """Singleton gains f_S(a) for the candidate subset ``idx`` only —
        lazy greedy's batched re-check oracle.  Same math as ``gains``
        (one fused sweep through the marginal-gains wrapper) over the
        gathered columns instead of the whole ground set."""
        g = self._gains_cols(state, jnp.take(self.X, idx, axis=1),
                             jnp.take(self.col_sq, idx))
        return jnp.where(state.sel_mask[idx], 0.0, g)

    # -- sample-batched filter engine (DASH inner loop) -------------------
    def expand_basis(self, state: RegressionState, idx, mask):
        """MGS deltas for S ∪ R without rewriting the shared basis.

        Runs the same accept rule as ``add_set`` but writes the new
        orthonormal columns into a fresh (d, m) buffer D (⊥ span(Q)), so
        the filter engine can reuse Q across all samples.  Returns
        (D, resid) — the delta basis and the updated residual.
        """
        C = gather_columns(self.X, idx, mask)                  # (d, m)
        return mgs_expand(
            state.Q, state.count, state.resid, C, self.kmax, self.span_tol
        )

    def filter_gains_batch(self, state: RegressionState, idx, mask):
        """Gains w.r.t. S ∪ R_i for every sample i in one fused pass.

        idx/mask: (n_samples, m) padded Monte-Carlo sets.  Returns the
        (n_samples, n) matrix ``jax.vmap(lambda R: gains(add_set(S, R)))``
        would produce, without re-projecting the shared basis per sample.

        Under the batched (OPT, α) lattice this whole method runs inside
        ``vmap`` over guesses; the ``filter_gains`` wrapper's
        custom-vmap rule then folds every guess's (Q, D, R) into ONE
        guess-axis engine launch (X streamed once for the lattice).
        """
        D, R = jax.vmap(lambda i, v: self.expand_basis(state, i, v))(idx, mask)
        if self.use_kernel:
            from repro.kernels.filter_gains.ops import filter_gains

            g = filter_gains(self.X, state.Q, D, R, self.col_sq,
                             precision=self.precision)
        else:
            from repro.kernels.filter_gains.ref import filter_gains_ref

            g = filter_gains_ref(quantize(self.X, self.precision), state.Q,
                                 D, R, self.col_sq)
        g = g / self.ysq
        sel = jax.vmap(
            lambda i, v: state.sel_mask.at[i].set(state.sel_mask[i] | v)
        )(idx, mask)
        return jnp.where(sel, 0.0, g)

    # -- distributed contract (column-based; see DistributedObjective) ----
    def dist_init(self, X_local) -> RegressionDistState:
        return RegressionDistState(
            Q=jnp.zeros((self.d, self.kmax), jnp.float32),
            count=jnp.zeros((), jnp.int32),
            resid=self.y,
            col_sq=jnp.sum(X_local * X_local, axis=0),
        )

    def dist_value(self, ds: RegressionDistState):
        return (self.ysq - jnp.sum(ds.resid * ds.resid)) / self.ysq

    def dist_gains(self, ds: RegressionDistState, X_local):
        # ops wrapper, not the inline ref: resolve_path routes each shard
        # to compiled Pallas on TPU and the jnp reference elsewhere.
        from repro.kernels.marginal_gains.ops import regression_gains

        return regression_gains(X_local, ds.Q, ds.resid, ds.col_sq,
                                precision=self.precision) / self.ysq

    def dist_set_gain(self, ds: RegressionDistState, C, mask):
        Ct = C - ds.Q @ (ds.Q.T @ C)
        csq = jnp.sum(C * C, axis=0)
        G = Ct.T @ Ct
        # Padded/in-span columns: pin the diagonal so Cholesky stays PD.
        diag_fix = jnp.where(mask & (csq > 0),
                             self.jitter * jnp.maximum(csq, 1.0), 1.0)
        G = G + jnp.diag(diag_fix)
        b = Ct.T @ ds.resid * mask
        L = jnp.linalg.cholesky(G)
        z = jax.scipy.linalg.solve_triangular(L, b, lower=True)
        return jnp.sum(z * z) / self.ysq

    def dist_add_set(self, ds: RegressionDistState, C, mask, X_local):
        C = C * mask.astype(C.dtype)[None, :]
        Q, count, resid = mgs_extend(
            ds.Q, ds.count, ds.resid, C, self.kmax, self.span_tol
        )
        return RegressionDistState(Q=Q, count=count, resid=resid,
                                   col_sq=ds.col_sq)

    def dist_filter_gains_batch(self, ds: RegressionDistState, Cs, masks,
                                X_local):
        Cs = Cs * masks.astype(Cs.dtype)[:, None, :]
        D, R = jax.vmap(
            lambda C: mgs_expand(ds.Q, ds.count, ds.resid, C, self.kmax,
                                 self.span_tol)
        )(Cs)
        from repro.kernels.filter_gains.ops import filter_gains

        return filter_gains(X_local, ds.Q, D, R, ds.col_sq,
                            precision=self.precision) / self.ysq

    # -- exact reference (tests) ------------------------------------------
    def brute_value(self, sel_idx) -> jnp.ndarray:
        """f(S) via full lstsq — oracle for property tests."""
        Xs = self.X[:, jnp.asarray(sel_idx)]
        w, *_ = jnp.linalg.lstsq(Xs, self.y, rcond=None)
        resid = self.y - Xs @ w
        return (self.ysq - jnp.sum(resid * resid)) / self.ysq
