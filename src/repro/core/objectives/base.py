"""Objective interface for statistical subset selection.

Every objective is a *functional* oracle over a fixed ground set of ``n``
columns (features or experiment stimuli).  The selection algorithms (DASH,
greedy, ...) only interact through this interface, so they are agnostic to
which of the paper's three applications (Cor. 7/8/9) is being optimized.

All methods are pure and jit-compatible; solution sets are carried in
fixed-capacity padded index vectors so the whole algorithm can live inside
``lax`` control flow and be ``shard_map``-ped over a device mesh.

State conventions
-----------------
``state`` is a NamedTuple specific to the objective with at least:
  * ``sel_mask``: (n,) bool — membership of the current solution S,
  * ``value``:    ()   f32 — f(S) (normalized where noted).

Set arguments are passed as ``(idx, mask)`` where ``idx`` is an int32
vector of column indices (padded arbitrarily) and ``mask`` a bool vector
marking the real entries.

Filter engine
-------------
Objectives may additionally implement the *sample-batched filter engine*
contract (``SupportsFilterEngine``) used by DASH's filter statistic
Ê_R[f_{S∪R}(a)]: a ``use_filter_engine`` flag plus

    filter_gains_batch(state, idx, mask) -> (n_samples, n)

where idx/mask are (n_samples, m) padded Monte-Carlo sets.  The method
must return exactly what ``jax.vmap(lambda R: gains(add_set(state, R)))``
would — same accept rules, same capacity semantics, same masking of
selected elements — but is free to decompose the perturbed states into
shared + per-sample parts so all samples ride one fused kernel launch
(``repro.kernels.filter_gains``).  ``core.dash._estimate_elem_gains``
dispatches on ``use_filter_engine`` and falls back to the per-sample
vmap path for objectives without the contract.

The contract composes with the (OPT, α) guess lattice for free: the
batched ``dash_auto`` vmaps the selection loop over guesses, and the
``repro.kernels.filter_gains`` ops wrappers register ``custom_vmap``
rules that fold the vmapped per-guess state operands into ONE launch
over the ``n_guesses·n_samples`` grid (the ground set X streams once
for the whole lattice) — an implementation of ``filter_gains_batch``
only needs to keep its per-sample decomposition expressed through
those wrappers.

Distributed contract
--------------------
``core.distributed.dash_distributed`` runs the SAME selection loop with
the ground-set columns sharded over a mesh axis — and the §5 baseline
twins (``greedy_distributed``, ``stochastic_greedy_distributed``,
``top_k_distributed``, ``random_distributed``) run against the SAME
six-method contract, so implementing it once gives an objective the
whole ``core.algorithms.select`` registry on both runtimes.  Inside ``shard_map``
an objective cannot index its global ``X`` — every shard sees only its
local column block, and sampled sets arrive as already-gathered column
matrices ``C`` (a psum of one-hot GEMMs, see ``one_hot_columns``).  The
``DistributedObjective`` contract is therefore *column-based*: the
replicated oracle state (no ``sel_mask`` — the runner keeps the
shard-local selection mask) plus oracles over ``(C, mask)`` and the
shard's local columns ``X_local``.  All six methods must be collective
free — pure shard-local/replicated dense math — so the runner alone
decides what is psum'd/pmean'd and the fused filter-engine sweep stays
a single launch per shard (see docs/distributed.md).
"""

from __future__ import annotations

from typing import Any, Protocol

import jax
import jax.numpy as jnp

Array = Any


class Objective(Protocol):
    """Protocol implemented by all subset-selection objectives."""

    n: int          # ground-set size
    kmax: int       # static capacity for |S|

    def init(self) -> Any:
        """State for S = ∅."""

    def value(self, state) -> Array:
        """f(S)."""

    def gains(self, state) -> Array:
        """(n,) vector of singleton marginals f_S(a); 0 for a ∈ S."""

    def set_gain(self, state, idx, mask) -> Array:
        """f_S(R) for the padded set R = idx[mask]."""

    def add_set(self, state, idx, mask):
        """State for S ∪ R."""


class SupportsSubsetGains(Objective, Protocol):
    """Objectives that evaluate singleton gains for a candidate SUBSET.

    ``gains_subset(state, idx) -> (len(idx),)`` must equal
    ``gains(state)[idx]`` while touching only the gathered columns —
    this is lazy greedy's batched re-check oracle (one fused sweep of B
    stale candidates instead of a full (d, n) pass per pop).  All three
    paper objectives and the diversity objectives implement it; callers
    must treat it as optional (fall back to ``gains(state)[idx]``).
    """

    def gains_subset(self, state, idx) -> Array:
        """(len(idx),) gains f_S(idx[j]); 0 for already-selected."""


class SupportsFilterEngine(Objective, Protocol):
    """Objectives that batch DASH's filter statistic over samples.

    ``RegressionObjective``, ``AOptimalityObjective`` and
    ``ClassificationObjective`` all implement this; the shared kernels
    live in ``repro.kernels.filter_gains``.

    ``precision`` is the streamed-operand policy ("f32"/"bf16",
    ``repro.kernels.common.PRECISIONS``) the objective passes to every
    kernel dispatch — bf16 streams the big HBM-bound operands in half
    precision with f32 accumulation, and the jnp reference branches
    quantize identically so both routes compute the same function.
    Callers opt in per run via :func:`with_precision` (which ``select()``
    and the ``dash*`` entry points apply from their ``precision=``
    argument) rather than mutating the objective.
    """

    use_filter_engine: bool
    precision: str

    def filter_gains_batch(self, state, idx, mask) -> Array:
        """(n_samples, n) gains w.r.t. S ∪ R_i for each sampled R_i —
        semantically ``vmap(lambda R: gains(add_set(state, R)))``."""


class DistributedObjective(Objective, Protocol):
    """Column-based oracle bundle for the sharded DASH runtime.

    Implemented by ``RegressionObjective``, ``AOptimalityObjective`` and
    ``ClassificationObjective``; consumed by
    ``core.distributed.dash_distributed``.  ``dstate`` is an
    objective-specific pytree that is REPLICATED across model-axis
    shards except for explicitly shard-local caches (e.g. the A-opt
    shared solve W = M⁻¹X_local); it carries no ``sel_mask``.  ``C`` is
    a (d, m) matrix of globally-gathered sample columns with invalid
    slots zeroed; ``mask`` is the (m,) replicated slot-validity vector.

    Methods must be free of collectives and must not read ``self.X`` /
    other (n,)-shaped globals — only ``X_local`` and (d,)-shaped
    replicated data — so they are safe to trace inside ``shard_map``.
    """

    X: Array        # (d, n) ground-set columns — sharded BY THE RUNNER

    def dist_init(self, X_local):
        """Replicated oracle state for S = ∅ (plus shard-local caches)."""

    def dist_value(self, dstate) -> Array:
        """f(S) from the replicated state."""

    def dist_gains(self, dstate, X_local) -> Array:
        """(n_local,) singleton marginals for this shard's candidates.

        Must route through the ``repro.kernels`` ops wrappers so
        ``resolve_path`` backend routing (compiled Pallas on TPU, jnp
        reference elsewhere) applies per shard."""

    def dist_set_gain(self, dstate, C, mask) -> Array:
        """f_S(R) for the gathered sample columns."""

    def dist_add_set(self, dstate, C, mask, X_local):
        """Replicated state for S ∪ R (same accept/capacity rules as
        ``add_set``; zero columns — padding — are never accepted)."""

    def dist_filter_gains_batch(self, dstate, Cs, masks, X_local) -> Array:
        """(n_samples, n_local) gains w.r.t. S ∪ R_i for this shard —
        the filter-engine sweep, one fused launch for all samples.
        ``Cs``/``masks`` stack ``n_samples`` gathered sets."""


# Per-object caches that neither a pytree view nor a precision view
# shares with its parent.
_CACHE_ATTRS = ("_precision_views", "_selection_runner_cache")


class PytreeObject:
    """Base of every objective: the instance is a JAX pytree.

    Attributes holding sizes, flags, floats and strings are static and
    part of the tree structure; every other attribute (the dataset
    arrays, derived caches, a nested objective such as a diversified
    objective's base) is a child.  So an objective is passed to
    ``jax.jit`` as an ARGUMENT: its dataset enters a compiled program as
    a parameter, never as a constant baked into the executable, and one
    compilation serves every objective of the same structure and shapes.
    """

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        jax.tree_util.register_pytree_node(
            cls, _flatten_object, lambda aux, leaves, _cls=cls:
                _unflatten_object(_cls, aux, leaves))


_STATIC_TYPES = (bool, int, float, str, type(None))


def _flatten_object(obj):
    names, leaves, static = [], [], []
    for name in sorted(vars(obj)):
        if name in _CACHE_ATTRS:
            continue
        v = vars(obj)[name]
        if isinstance(v, _STATIC_TYPES):
            static.append((name, v))
        else:
            names.append(name)
            leaves.append(v)
    return leaves, (tuple(names), tuple(static))


def _unflatten_object(cls, aux, leaves):
    names, static = aux
    obj = object.__new__(cls)
    obj.__dict__.update(static)
    obj.__dict__.update(zip(names, leaves))
    return obj


def with_precision(obj, precision: str | None):
    """A view of ``obj`` running its kernels at ``precision``.

    Returns ``obj`` itself when the policy already matches (so f32 — the
    default everywhere — costs nothing); otherwise a memoized shallow
    copy with ``precision`` overridden.  The copy drops the two
    per-object caches a view must NOT share with its parent:
    ``_precision_views`` (a view holds no views) and the
    ``cached_runner`` store (``_selection_runner_cache``), whose compiled
    runners closed over the parent's precision.  Memoizing the view on
    the parent keeps its identity stable across calls, so the view's OWN
    runner cache stays warm run to run.
    """
    from repro.kernels.common import resolve_precision

    p = resolve_precision(precision)
    if getattr(obj, "precision", "f32") == p:
        return obj
    views = obj.__dict__.setdefault("_precision_views", {})
    if p not in views:
        view = object.__new__(type(obj))
        view.__dict__.update(obj.__dict__)
        view.__dict__.pop("_precision_views", None)
        view.__dict__.pop("_selection_runner_cache", None)
        view.precision = p
        views[p] = view
    return views[p]


def normalize_columns(X: Array, eps: float = 1e-12) -> Array:
    """Zero-mean, unit-variance columns (paper's preprocessing for D1-D4)."""
    X = X - jnp.mean(X, axis=0, keepdims=True)
    nrm = jnp.sqrt(jnp.sum(X * X, axis=0, keepdims=True))
    return X / jnp.maximum(nrm, eps)


def one_hot_columns(idx: Array, mask: Array, n: int) -> Array:
    """(n, m) selection matrix E with E[idx[j], j] = mask[j].

    ``X @ E`` gathers the padded set's columns — this formulation keeps the
    gather expressible as a GEMM, which is what the distributed oracle uses
    to fetch remote columns with a single ``psum`` (see core/distributed.py).
    """
    m = idx.shape[0]
    e = jnp.zeros((n, m), dtype=jnp.float32)
    e = e.at[idx, jnp.arange(m)].add(mask.astype(jnp.float32))
    return e


def gather_columns(X: Array, idx: Array, mask: Array) -> Array:
    """(d, m) columns X[:, idx] with padded entries zeroed."""
    cols = jnp.take(X, idx, axis=1)
    return cols * mask.astype(X.dtype)[None, :]


def write_accepted_column(Q: Array, slot, accept, q: Array) -> Array:
    """Write basis column ``q`` into ``Q[:, slot]`` only when ``accept``.

    The guarded read-modify-write all incremental-MGS loops share: a
    rejected candidate (at capacity, in-span, or padded) must leave the
    column already stored at ``slot`` untouched — an unguarded
    ``dynamic_update_slice`` would clobber it with zeros.
    """
    prev = jax.lax.dynamic_slice(Q, (0, slot), (Q.shape[0], 1))
    col = jnp.where(accept, q[:, None], prev)
    return jax.lax.dynamic_update_slice(Q, col, (0, slot))
