"""Selection-algorithm registry — one entry point for every §5 competitor.

The paper's experiments are head-to-head comparisons: DASH vs SDS_MA
greedy, TOP-k and RANDOM (plus lazy and stochastic greedy as the strong
practical competitors of Khanna et al. / Breuer et al.).  This module
owns the roster once:

    from repro.core import select
    res = select("greedy", obj, k)                  # single device
    res = select("greedy", obj, k, mesh=mesh)       # sharded

Every algorithm is registered as an :class:`AlgorithmSpec` pairing its
single-device implementation with its distributed twin (expressed
against the ``DistributedObjective`` contract — see
``core.distributed``), plus an adaptivity/query cost model for
docs/algorithms.md.  ``select`` dispatches on ``mesh`` and normalizes
every native result type into one :class:`SelectionResult` so
benchmarks, tests and serving code can loop over algorithms without
per-algorithm unpacking.

Adding an algorithm = one ``register(AlgorithmSpec(...))`` call; the
registry tests and the parity tests iterate the registry, so a new
entry is tested for free.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.adaptive_sequencing import adaptive_sequencing
from repro.core.baselines import random_select, top_k_select
from repro.core.fast import fast, fast_cost
from repro.core.greedy import (
    greedy,
    greedy_parallel_cost,
    greedy_sequential_cost,
    lazy_greedy,
    lazy_greedy_cost,
    stochastic_greedy,
    stochastic_greedy_cost,
)


class SelectionResult(NamedTuple):
    """Normalized result of :func:`select`.

    ``values`` is the per-round f(S) trace when the algorithm has one
    (DASH rounds, greedy picks) and an empty (0,) array for the one-shot
    selectors.  ``raw`` keeps the algorithm's native result (DashResult,
    GreedyResult, DistSelectResult, ...) for callers that need
    algorithm-specific fields (traces, states, lattices).
    """

    sel_mask: jnp.ndarray
    sel_count: jnp.ndarray
    value: jnp.ndarray
    values: jnp.ndarray
    raw: Any


@dataclass(frozen=True)
class AlgorithmSpec:
    """Registry entry: the single-device / distributed pair + metadata.

    ``single(obj, k, key, **opts)`` and
    ``distributed(obj, k, key, mesh, **opts)`` both return their native
    result type; ``select`` normalizes.  ``needs_key`` marks randomized
    algorithms (``select`` defaults their key deterministically).
    ``cost(n, k)`` returns the ``{"oracle_calls", "adaptive_rounds"}``
    accounting used by docs/algorithms.md.
    """

    name: str
    single: Callable[..., Any]
    distributed: Callable[..., Any] | None
    needs_key: bool
    cost: Callable[[int, int], dict]
    summary: str


_REGISTRY: dict[str, AlgorithmSpec] = {}


def register(spec: AlgorithmSpec) -> AlgorithmSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f"algorithm {spec.name!r} already registered")
    _REGISTRY[spec.name] = spec
    return spec


def available_algorithms(*, distributed: bool | None = None) -> tuple[str, ...]:
    """Registered names, optionally only those with a distributed twin."""
    return tuple(
        name for name, spec in _REGISTRY.items()
        if distributed is None or (spec.distributed is not None) == distributed
    )


def get_algorithm(name: str) -> AlgorithmSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown algorithm {name!r}; available: "
            f"{sorted(_REGISTRY)}"
        ) from None


def algorithm_cost(name: str, n: int, k: int) -> dict:
    """{"oracle_calls", "adaptive_rounds"} for the algorithm at (n, k)."""
    return get_algorithm(name).cost(n, k)


def _normalize(raw) -> SelectionResult:
    sel_mask = raw.sel_mask
    count = getattr(raw, "sel_count", None)
    if count is None:
        count = jnp.sum(sel_mask.astype(jnp.int32))
    values = getattr(raw, "values", None)
    if values is None:
        trace = getattr(raw, "trace", None)
        values = (trace.values if trace is not None
                  else jnp.zeros((0,), jnp.float32))
    return SelectionResult(
        sel_mask=sel_mask, sel_count=count, value=raw.value,
        values=values, raw=raw,
    )


def _validate_k(k) -> int:
    """k must be a positive integer — fail here with a clear message
    instead of deep inside a jit trace (lax.top_k / fori_loop errors)."""
    ki = int(k)
    if ki <= 0:
        raise ValueError(f"k must be a positive integer, got {k!r}")
    return ki


def _validate_mesh(obj, mesh, algo: str) -> None:
    """Mesh dispatch preconditions, checked loudly before tracing.

    A mismatched objective/mesh used to die deep inside ``shard_map``
    with a shape error; the serving layer (and any caller) gets a clear
    ``ValueError`` naming the fix instead.
    """
    if not hasattr(obj, "dist_init"):
        raise ValueError(
            f"objective {type(obj).__name__} does not implement the "
            f"DistributedObjective contract (dist_init/...), so "
            f"select({algo!r}, ..., mesh=...) cannot dispatch the "
            f"distributed twin"
        )
    X = getattr(obj, "X", None)
    try:
        axes = dict(mesh.shape)
    except (AttributeError, TypeError):
        raise ValueError(
            f"mesh must expose a named-axis .shape mapping, got "
            f"{type(mesh).__name__}"
        ) from None
    model = int(axes.get("model", 1) or 1)
    if X is not None and model > 1 and X.shape[1] % model:
        raise ValueError(
            f"ground set n={X.shape[1]} does not divide the mesh's "
            f"model axis ({model}) — pad_ground_set the columns first"
        )


def select(algo: str, obj, k: int, key=None, mesh=None, **opts) -> SelectionResult:
    """Run any registered selection algorithm — THE entry point.

    ``mesh=None`` runs the single-device implementation; passing a mesh
    dispatches to the distributed twin (the objective must implement the
    ``DistributedObjective`` contract and ``obj.X``'s column count must
    divide the mesh's model axis — ``pad_ground_set`` first if needed).

    ``key`` seeds the randomized algorithms (dash, stochastic_greedy,
    random); when omitted it defaults to ``PRNGKey(0)`` so every
    algorithm is runnable with the same two-argument call.  Extra
    ``**opts`` pass through to the implementation (e.g. ``subsample=``
    for stochastic greedy, ``n_guesses=``/``opt=`` for dash,
    ``model_axis=`` for any distributed twin).

    ``precision="bf16"`` opts the run into bf16 streaming of the
    HBM-bound kernel operands (f32 accumulation) by swapping ``obj`` for
    its :func:`~repro.core.objectives.base.with_precision` view before
    dispatch — it applies uniformly to every registered algorithm on
    both runtimes.

    The call is one ``repro.select`` profiler span (``algo`` and ``k``
    as its arguments) on the host.
    """
    with TraceAnnotation("repro.select", algo=algo, k=k):
        return _select(algo, obj, k, key, mesh, opts)


def _select(algo, obj, k, key, mesh, opts) -> SelectionResult:
    spec = get_algorithm(algo)
    k = _validate_k(k)
    precision = opts.pop("precision", None)
    if precision is not None:
        from repro.core.objectives.base import with_precision

        obj = with_precision(obj, precision)
    if spec.needs_key and key is None:
        key = jax.random.PRNGKey(0)
    if mesh is None:
        return _normalize(spec.single(obj, k, key, **opts))
    if spec.distributed is None:
        raise ValueError(f"algorithm {algo!r} has no distributed twin")
    _validate_mesh(obj, mesh, algo)
    return _normalize(spec.distributed(obj, k, key, mesh, **opts))


# ---------------------------------------------------------------------------
# the §5 roster
# ---------------------------------------------------------------------------

def _dash_single(obj, k, key, **opts):
    from repro.core.dash import DashConfig, dash, dash_auto

    opt = opts.pop("opt", None)
    if opt is not None:
        cfg_keys = ("r", "eps", "alpha", "n_samples", "trim_frac",
                    "max_filter_iters")
        cfg = DashConfig(k=k, **{kk: opts.pop(kk) for kk in cfg_keys
                                 if kk in opts})
        return dash(obj, cfg, key, opt, **opts)
    return dash_auto(obj, k, key, **opts)


def _dash_distributed(obj, k, key, mesh, **opts):
    from repro.core.dash import DashConfig
    from repro.core.distributed import dash_auto_distributed, dash_distributed

    opt = opts.pop("opt", None)
    if opt is not None:
        cfg_keys = ("r", "eps", "alpha", "n_samples", "trim_frac",
                    "max_filter_iters")
        cfg = DashConfig(k=k, **{kk: opts.pop(kk) for kk in cfg_keys
                                 if kk in opts})
        return dash_distributed(obj, cfg, key, opt, mesh, **opts)
    if "pod" not in mesh.shape:
        raise ValueError(
            "select('dash', ..., mesh=...) without opt= sweeps the (OPT, α) "
            "guess lattice over the mesh's 'pod' axis — build the mesh with "
            "make_lattice_mesh, or pass an explicit opt= guess for a "
            "(data, model) mesh"
        )
    return dash_auto_distributed(obj, k, key, mesh, **opts)


def _dash_cost(n: int, k: int) -> dict:
    # Thm 10: O(log n) adaptive rounds, O(n log n) oracle queries (each
    # round's filter sweeps the ≤ n survivors a logarithmic number of
    # times); reported at the paper's leading order.
    import math

    r = max(1, min(k, int(math.ceil(math.log2(max(n, 2))))))
    return {"oracle_calls": n * r, "adaptive_rounds": r}


register(AlgorithmSpec(
    name="dash",
    single=_dash_single,
    distributed=_dash_distributed,
    needs_key=True,
    cost=_dash_cost,
    summary="Alg. 1 adaptive sampling: O(log n) rounds, "
            "(1-1/e^{α²}-ε)·OPT for α-differentially-submodular f",
))

register(AlgorithmSpec(
    name="greedy",
    single=lambda obj, k, key, **o: greedy(obj, k, **o),
    distributed=lambda obj, k, key, mesh, **o: _dist().greedy_distributed(
        obj, k, mesh, key=key, **o),
    needs_key=False,
    cost=greedy_parallel_cost,
    summary="parallel SDS_MA: k rounds, batched argmax per round, "
            "(1-1/e^{γ}) via weak submodularity",
))

register(AlgorithmSpec(
    name="lazy_greedy",
    single=lambda obj, k, key, **o: lazy_greedy(obj, k, **o),
    distributed=None,
    needs_key=False,
    cost=lazy_greedy_cost,
    summary="Minoux lazy bounds with batched re-checks; exact for "
            "submodular f (host-driven — no distributed twin)",
))

register(AlgorithmSpec(
    name="stochastic_greedy",
    single=lambda obj, k, key, **o: stochastic_greedy(obj, k, key, **o),
    distributed=lambda obj, k, key, mesh, **o:
        _dist().stochastic_greedy_distributed(obj, k, key, mesh, **o),
    needs_key=True,
    cost=stochastic_greedy_cost,
    summary="Mirzasoleiman subsampled argmax: k rounds of "
            "⌈(n/k)ln(1/ε)⌉ queries, (1-1/e-ε) expected",
))

register(AlgorithmSpec(
    name="topk",
    single=lambda obj, k, key, **o: top_k_select(obj, k, **o),
    distributed=lambda obj, k, key, mesh, **o: _dist().top_k_distributed(
        obj, k, mesh, key=key, **o),
    needs_key=False,
    cost=lambda n, k: {"oracle_calls": n, "adaptive_rounds": 1},
    summary="largest k singleton values in one sweep; γ²-approximation "
            "for feature selection (App. J)",
))

def _adseq_cost(n: int, k: int) -> dict:
    # Same leading order as DASH: the BRS round cap is min(k, ⌈log₂ n⌉)
    # and each round's fused prefix sweep touches ≤ n candidates.
    import math

    r = max(1, min(k, int(math.ceil(math.log2(max(n, 2))))))
    return {"oracle_calls": n * r, "adaptive_rounds": r}


register(AlgorithmSpec(
    name="fast",
    single=lambda obj, k, key, **o: fast(obj, k, key, **o),
    distributed=lambda obj, k, key, mesh, **o: _dist().fast_distributed(
        obj, k, key, mesh, **o),
    needs_key=True,
    cost=fast_cost,
    summary="Breuer et al. FAST: adaptive sequencing + binary-search "
            "threshold ladder, prefix sweeps fused through the filter "
            "engine (prefixes ≈ samples)",
))

register(AlgorithmSpec(
    name="adaptive_sequencing",
    single=lambda obj, k, key, **o: adaptive_sequencing(obj, k, key, **o),
    distributed=None,
    needs_key=True,
    cost=_adseq_cost,
    summary="BRS adaptive sequencing with the residual (OPT − f(S)) "
            "threshold — the single-runtime substrate fast builds on",
))

register(AlgorithmSpec(
    name="random",
    single=lambda obj, k, key, **o: random_select(obj, k, key, **o),
    distributed=lambda obj, k, key, mesh, **o: _dist().random_distributed(
        obj, k, key, mesh, **o),
    needs_key=True,
    cost=lambda n, k: {"oracle_calls": 1, "adaptive_rounds": 1},
    summary="uniform without-replacement sample (Gumbel top-k) — the "
            "§5 floor",
))


def _dist():
    # Deferred: core.distributed imports shard_map machinery; keep the
    # registry importable (and the single-device path usable) without it.
    from repro.core import distributed

    return distributed


# ---------------------------------------------------------------------------
# request-batched dispatch — the serving substrate
# ---------------------------------------------------------------------------

_DASH_CFG_KEYS = ("r", "eps", "alpha", "n_samples", "trim_frac",
                  "max_filter_iters")


def select_batched(algo: str, obj, k: int, keys, *, opt=None, alpha=None,
                   **opts) -> SelectionResult:
    """Fold B independent ``(key[, opt, alpha])`` requests against ONE
    objective into ONE compiled launch — the request-batched entry the
    selection service (``repro.serve``) builds on.

    The request axis is just another leading fold through the existing
    machinery: randomized algorithms ``vmap`` their single-device
    implementation over the keys (for dash, the filter-engine
    ``custom_vmap`` rules collapse every request's Monte-Carlo sweep
    into one fused kernel launch, exactly as the (OPT, α) guess lattice
    does), and deterministic algorithms (greedy, topk) run once and
    broadcast — their lanes are provably identical.  Returns a
    :class:`SelectionResult` whose every field carries a leading
    ``(B,)`` request axis.

    ``opt``/``alpha`` apply to dash only: scalars broadcast, arrays are
    per-request.  Batched dash requires an explicit ``opt`` (per-request
    lattice sweeps belong to ``dash_auto``; a serving layer derives OPT
    once per dataset — see ``repro.serve``).  ``lazy_greedy`` is
    host-driven and cannot be request-batched.  Compiled runners are
    cached per objective (``cached_runner``), keyed on
    ``(algo, k, B, opts)`` — repeat traffic at a warm bucket shape adds
    zero retraces.
    """
    from repro.core.selection_loop import cached_runner

    spec = get_algorithm(algo)
    k = _validate_k(k)
    if algo == "lazy_greedy":
        raise ValueError(
            "lazy_greedy is host-driven (data-dependent re-check order) "
            "and cannot be request-batched; use greedy or topk"
        )
    precision = opts.pop("precision", None)
    if precision is not None:
        from repro.core.objectives.base import with_precision

        obj = with_precision(obj, precision)

    keys = jnp.asarray(keys)
    if keys.ndim == 1:
        keys = keys[None]
    B = keys.shape[0]

    if not spec.needs_key:
        opts_key = tuple(sorted(opts.items()))
        runner = cached_runner(
            obj, ("select_batched_det", algo, k, opts_key),
            lambda: jax.jit(lambda o: spec.single(o, k, None, **opts)),
        )
        res = _normalize(runner(obj))
        return jax.tree_util.tree_map(
            lambda x: jnp.broadcast_to(x, (B,) + jnp.shape(x)), res
        )

    if algo == "dash":
        if opt is None:
            raise ValueError(
                "request-batched dash needs an explicit opt= guess "
                "(scalar or (B,) per-request array) — derive one via a "
                "topk probe or opt_guess_lattice"
            )
        from repro.core.dash import DashConfig, dash

        cfg = DashConfig(k=k, **{kk: opts.pop(kk) for kk in _DASH_CFG_KEYS
                                 if kk in opts})
        if opts:
            raise ValueError(f"unknown dash options: {sorted(opts)}")
        opt = jnp.broadcast_to(
            jnp.asarray(opt, jnp.float32).reshape(-1), (B,))
        alpha = jnp.broadcast_to(
            jnp.asarray(cfg.alpha if alpha is None else alpha,
                        jnp.float32).reshape(-1), (B,))
        runner = cached_runner(
            obj, ("select_batched", "dash", k, B, cfg),
            lambda: jax.jit(jax.vmap(
                lambda o, kk, g, a: dash(o, cfg, kk, g, a),
                in_axes=(None, 0, 0, 0))),
        )
        return _normalize(runner(obj, keys, opt, alpha))

    opts_key = tuple(sorted(opts.items()))
    # Normalize INSIDE the vmap so sel_count is per-request, not a sum
    # over the whole batch of masks.
    runner = cached_runner(
        obj, ("select_batched", algo, k, B, opts_key),
        lambda: jax.jit(jax.vmap(
            lambda o, kk: _normalize(spec.single(o, k, kk, **opts)),
            in_axes=(None, 0))),
    )
    return runner(obj, keys)
