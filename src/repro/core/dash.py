"""DASH — Differentially-Adaptive-Sampling (paper Algorithm 1, Thm 10).

For α-differentially-submodular objectives (Definition 1 — the sandwich
α²·g(S ∪ T) − α²·g(S) ≤ f_S(T) ≤ g(S ∪ T) − g(S) for a submodular g;
Corollaries 7/8/9 prove α for regression, classification and A-optimal
design), DASH achieves f(S) ≥ (1 − 1/e^{α²} − ε)·OPT in O(log n)
adaptive rounds — the exponential speedup over greedy's k sequential
rounds that is the point of the paper.

The round/filter control flow itself (outer rounds, the thresholded
inner while loop with the Lemma-21 iteration cap, trace bookkeeping)
lives in ``core.selection_loop`` and is SHARED with the distributed
runtime (``core.distributed``): this module only binds the loop to a
single-device objective — Monte-Carlo estimators over ``obj``'s batched
oracles and a Gumbel-top-k sampler over the ground set.

The filter statistic Ê_R[f_{S∪R}(a)] — a fresh batched gain oracle at
every Monte-Carlo perturbed state S ∪ R_i — dominates the cost of each
inner iteration; ``_estimate_elem_gains`` routes it through the
sample-batched filter engine (``repro.kernels.filter_gains``) whenever
the objective opts in via its ``use_filter_engine`` flag.

Differences from the idealized listing (all from the paper's App. G):
  * expectations are Monte-Carlo estimates over ``n_samples`` sets
    (straggler-robust trimmed mean optional),
  * OPT and α are guessed — ``dash_auto`` runs a (1+ε)^i lattice of
    (OPT, α) guesses and returns the best solution; by default the WHOLE
    lattice is one jitted vmapped computation (device-side argmax, the
    filter sweeps folded into single guess-axis engine launches), and
    ``core.distributed.dash_auto_distributed`` maps the same lattice
    onto the ``pod`` mesh axis,
  * the filter estimates E_R[f_{S∪(R\\{a})}(a)] by evaluating the batched
    gain vector at S∪R_i for each sample i and averaging over only the
    samples with a ∉ R_i (exact leave-one-out semantics for the samples
    that matter, with the current-state gain as fallback when every
    sample contains a — probability ≤ (block/|X|)^m),
  * the inner while loop carries the Lemma-21 iteration cap
    ⌈log_{1+ε/2} n⌉ so the compiled control flow is total even for
    non-differentially-submodular inputs (App. A.2's failure mode).

Everything is fixed-shape and jit/vmap/shard_map-compatible.
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.estimators import (
    sample_set_batch,
    sample_set_from_mask,
    trimmed_mean,
)
from repro.core.objectives.base import with_precision
from repro.core.selection_loop import (  # noqa: F401  (re-exported API)
    DashConfig,
    DashTrace,
    ResilienceConfig,
    SelectionCarry,
    SelectionHooks,
    cached_runner,
    drive_checkpointed_rounds,
    initial_carry,
    make_round_body,
    run_selection_rounds,
)


class DashResult(NamedTuple):
    sel_mask: jnp.ndarray      # (n,) bool
    sel_count: jnp.ndarray     # () int32
    value: jnp.ndarray         # () f32
    rounds: jnp.ndarray        # () int32 — adaptive rounds consumed
    trace: DashTrace
    state: Any


def _estimate_set_gain(obj, state, alive, block, allowed, key, cfg):
    """Ê_{R~U(X)}[f_S(R)] over cfg.n_samples Monte-Carlo sets."""
    keys = jax.random.split(key, cfg.n_samples)

    def one(k):
        idx, valid = sample_set_from_mask(k, alive, block)
        valid = valid & (jnp.arange(block) < allowed)
        return obj.set_gain(state, idx, valid)

    vals = jax.vmap(one)(keys)
    return trimmed_mean(vals, cfg.trim_frac)


def _estimate_elem_gains(obj, state, alive, block, allowed, key, cfg):
    """Ê_R[f_{S∪(R\\{a})}(a)] for every a — the filter statistic.

    Estimator: draw ``cfg.n_samples`` i.i.d. sets R_i ~ U(X), evaluate
    the batched gain vector at each perturbed state S ∪ R_i, and average
    per candidate over only the samples with a ∉ R_i (weight matrix
    below) — exact leave-one-out semantics for the samples that matter,
    with the current-state gain as fallback when every sample contains
    a.  This is the Alg. 1 filter expectation of App. G.

    Objectives exposing ``filter_gains_batch`` (gated by their
    ``use_filter_engine`` flag — regression, A-optimality and logistic
    all do) evaluate all ``n_samples`` perturbed states in one fused
    pass (repro.kernels.filter_gains); everything else takes the
    per-sample add_set + gains path via vmap.
    """
    n = alive.shape[0]
    idx, valid = sample_set_batch(key, alive, block, cfg.n_samples)
    valid = valid & (jnp.arange(block) < allowed)[None, :]  # (m, block)

    if getattr(obj, "use_filter_engine", False):
        gains = obj.filter_gains_batch(state, idx, valid)
    else:
        def perturbed_gains(i, v):
            with jax.named_scope("repro.add_set"):
                perturbed = obj.add_set(state, i, v)
            return obj.gains(perturbed)

        gains = jax.vmap(perturbed_gains)(idx, valid)  # (m, n) w.r.t. S∪R

    weights = jax.vmap(                         # weight 0 where a ∈ R
        lambda i, v: jnp.ones((n,)).at[i].add(jnp.where(v, -1.0, 0.0))
    )(idx, valid)
    wsum = jnp.sum(weights, axis=0)
    est = jnp.sum(gains * weights, axis=0) / jnp.maximum(wsum, 1.0)
    # Fallback for elements present in every sample: current-state gain.
    return jnp.where(wsum > 0, est, obj.gains(state))


def _single_device_hooks(obj, cfg: DashConfig) -> SelectionHooks:
    """Bind the shared selection loop to a single-device objective."""
    block = cfg.block

    def pick_and_add(state, alive, allowed, key):
        idx, valid = sample_set_from_mask(key, alive, block)
        valid = valid & (jnp.arange(block) < allowed)
        with jax.named_scope("repro.add_set"):
            state = obj.add_set(state, idx, valid)
        return state, jnp.sum(valid.astype(jnp.int32))

    return SelectionHooks(
        value=obj.value,
        sel_mask=lambda state: state.sel_mask,
        estimate_set_gain=lambda state, alive, allowed, key:
            _estimate_set_gain(obj, state, alive, block, allowed, key, cfg),
        estimate_elem_gains=lambda state, alive, allowed, key:
            _estimate_elem_gains(obj, state, alive, block, allowed, key, cfg),
        pick_and_add=pick_and_add,
    )


def dash(obj, cfg: DashConfig, key, opt: float | jnp.ndarray,
         alpha: jnp.ndarray | None = None, *,
         precision: str | None = None) -> DashResult:
    """Run DASH for a single (OPT, α) guess.  jit/vmap-compatible.

    ``alpha`` optionally overrides ``cfg.alpha`` with a traced value so
    the (OPT, α) lattice can vmap over both guess axes at once.
    ``precision`` optionally overrides the objective's streamed-operand
    kernel policy for this run (see ``objectives.base.with_precision``).
    """
    if precision is not None:
        obj = with_precision(obj, precision)
    cfg = cfg.resolve(obj.n)
    hooks = _single_device_hooks(obj, cfg)
    state, alive, count, key, trace = run_selection_rounds(
        hooks, cfg, opt, key, obj.init(), jnp.ones((obj.n,), bool),
        alpha=alpha,
    )
    return DashResult(
        sel_mask=state.sel_mask,
        sel_count=count,
        value=obj.value(state),
        rounds=jnp.sum(trace.filter_iters) + cfg.r,
        trace=trace,
        state=state,
    )


def _checkpointed_step_runner(obj, cfg: DashConfig):
    """One jitted DASH round with (ρ, OPT, α) as runtime inputs — a
    single compilation serves every round of every resumed run."""
    def step(o, rho, carry, opt, alpha):
        body = make_round_body(_single_device_hooks(o, cfg), cfg)
        return body(rho, carry, opt, alpha)

    return cached_runner(obj, ("ckpt_step", cfg), lambda: jax.jit(step))


def dash_checkpointed(
    obj, cfg: DashConfig, key, opt: float | jnp.ndarray,
    *, resilience: ResilienceConfig, alpha: jnp.ndarray | None = None,
    resume: bool = False, failure_injector=None, deadline=None,
    precision: str | None = None,
) -> DashResult:
    """Single-device DASH stepped round-by-round from the host, with the
    :class:`SelectionCarry` snapshotted at every round boundary.

    Semantically :func:`dash` (same hooks, same per-round body — the
    host ``for`` replaces the ``fori_loop``), traded for restartability:
    kill the process anywhere and ``resume=True`` replays from the
    newest complete snapshot in ``resilience.ckpt_dir`` to the SAME
    selected set the uninterrupted run commits (each round is a pure
    function of the carry, and the carry is exactly what's saved).
    Straggler simulation (``resilience.drop_rate``) only affects the
    distributed runtime; here the responder mask is ignored.
    """
    if precision is not None:
        obj = with_precision(obj, precision)
    cfg = cfg.resolve(obj.n)
    step = _checkpointed_step_runner(obj, cfg)
    alpha_v = jnp.asarray(cfg.alpha if alpha is None else alpha, jnp.float32)
    opt_v = jnp.asarray(opt, jnp.float32)
    carry = initial_carry(cfg, key, obj.init(), jnp.ones((obj.n,), bool))
    start_round = 0
    if resume and resilience.ckpt_dir:
        from repro.ckpt.checkpoint import (
            latest_complete_step,
            read_manifest,
            restore_checkpoint,
        )

        snap = latest_complete_step(resilience.ckpt_dir)
        if snap is not None:
            carry, _ = restore_checkpoint(resilience.ckpt_dir, carry,
                                          step=snap)
            start_round = int(
                read_manifest(resilience.ckpt_dir, snap)["extra"]["round"])

    carry = drive_checkpointed_rounds(
        lambda rho, c, arrived: step(obj, rho, c, opt_v, alpha_v),
        carry, cfg, resilience=resilience, start_round=start_round,
        failure_injector=failure_injector, deadline=deadline,
        snapshot_extra={"algo": "dash", "n": int(obj.n)},
    )
    state, _, count, _, trace = carry
    return DashResult(
        sel_mask=state.sel_mask,
        sel_count=count,
        value=obj.value(state),
        rounds=jnp.sum(trace.filter_iters) + cfg.r,
        trace=trace,
        state=state,
    )


def opt_guess_lattice(obj, eps: float, n_guesses: int, k: int | None = None,
                      *, top_gain=None):
    """OPT guesses spanning [max_a f(a), k·max_a f(a)] geometrically.

    The paper (App. G) uses OPT ∈ {(1+ε)^i·max_a f(a) : i ≤ ln(n)/ε};
    with a budgeted number of guesses we cover the same feasible range
    [g0, k·g0] (monotonicity ⇒ OPT ≥ g0; the modular upper bound of the
    sandwich ⇒ OPT ≲ k·g0) with geometric spacing — equivalent up to the
    (1+ε) granularity the analysis needs.

    A single guess gets the geometric midpoint of [g0, hi·g0] — the
    minimax-regret point of the range in log space.  (The old ratio
    formula's ``1/max(n_guesses − 1, 1)`` exponent silently pinned
    ``n_guesses=1`` to the degenerate lower endpoint g0.)

    ``top_gain`` is max_a f(a) when the caller already has it — the
    sharded runtimes compute it shard by shard, since a sweep of the
    whole ground set would gather X onto every device.
    """
    if top_gain is None:
        top_gain = jnp.max(obj.gains(obj.init()))
    g0 = jnp.maximum(top_gain, 1e-12)
    hi = float(k) if k else 1.0 / eps
    if n_guesses == 1:
        return g0 * jnp.sqrt(jnp.asarray(hi, jnp.float32))[None]
    ratio = jnp.asarray(hi, jnp.float32) ** (1.0 / (n_guesses - 1))
    i = jnp.arange(n_guesses, dtype=jnp.float32)
    return g0 * ratio ** i


def lattice_grid(guesses, alphas):
    """Cross product of the OPT lattice with an α lattice.

    Returns ``(opts, alphas)`` flattened to one leading guess axis of
    size ``n_guesses · n_alphas``, OPT-major (all α for guess 0 first) —
    the layout every lattice runner (batched vmap, pod axis) uses.
    """
    guesses = jnp.asarray(guesses, jnp.float32).reshape(-1)
    alphas = jnp.asarray(alphas, jnp.float32).reshape(-1)
    g, a = guesses.shape[0], alphas.shape[0]
    return (jnp.repeat(guesses, a),
            jnp.tile(alphas, g))


def nan_to_neginf(v):
    """Guard lattice argmaxes: a numerically degenerate guess lane
    (value = NaN) must never win — jnp.argmax would return the NaN
    index, where the historical host-side ``float(a) > float(b)`` sweep
    skipped it."""
    return jnp.where(jnp.isnan(v), -jnp.inf, v)


def _best_of_lattice(results: DashResult) -> DashResult:
    """Device-side argmax over the leading guess axis — no host sync."""
    best = jnp.argmax(nan_to_neginf(results.value))
    return jax.tree_util.tree_map(lambda x: x[best], results)


def _lattice_runner(obj, cfg: DashConfig):
    """Jitted lattice executor ``run(obj, keys, opts, alphas)``, cached
    per objective (weakly — see :func:`core.selection_loop.cached_runner`).

    ``dash_auto`` is called repeatedly with the same objective (guess
    sweeps, benchmarks, retries with fresh keys); building the jit
    wrapper inline would discard XLA's compilation cache every call and
    turn each invocation into a full retrace.  The objective is the
    first ARGUMENT, so its dataset is a parameter of the program.
    """
    def run(o, kk, g, a):
        return dash(o, cfg, kk, g, a)

    return cached_runner(
        obj, ("lattice", cfg),
        lambda: jax.jit(jax.vmap(run, in_axes=(None, 0, 0, 0))))


def dash_auto(
    obj,
    k: int,
    key,
    *,
    eps: float = 0.2,
    alpha: float = 0.5,
    r: int = 0,
    n_samples: int = 8,
    n_guesses: int = 8,
    trim_frac: float = 0.0,
    alphas=None,
    return_lattice: bool = False,
    precision: str | None = None,
):
    """DASH with the (OPT, α) guess lattice; returns the best solution.

    The WHOLE lattice runs as one jitted vmapped computation: all
    guesses' selection loops advance in lockstep under a single
    compilation, the filter sweeps ride the guess-folded filter engine
    (one fused launch for all G·n_samples perturbed states — see
    ``repro.kernels.filter_gains``), and the best guess is committed by
    a device-side argmax, so the host never syncs per guess.  To look at
    one guess alone, run ``jax.jit(dash, static_argnums=1)`` on that
    lane's key, OPT and α.

    ``alphas`` optionally adds an α lattice: the runs sweep the full
    (OPT, α) cross product (``n_guesses · len(alphas)`` joint guesses),
    which is how App. G treats the unknown differential-submodularity
    parameter.  ``return_lattice=True`` additionally returns the stacked
    per-guess :class:`DashResult` (leading axis = joint guess, OPT-major)
    for diagnostics and parity tests.

    On the host, the guess set-up, the lattice's dispatch and the argmax
    are the profiler spans ``repro.dash.guesses``, ``repro.dash.lattice``
    and ``repro.dash.best``.
    """
    if precision is not None:
        # Applied before the lattice runner so the compiled runner is
        # cached on (and keyed by) the precision view.
        obj = with_precision(obj, precision)
    cfg = DashConfig(k=k, r=r, eps=eps, alpha=alpha, n_samples=n_samples,
                     trim_frac=trim_frac)
    with TraceAnnotation("repro.dash.guesses"):
        guesses = opt_guess_lattice(obj, eps, n_guesses, k)
        opts, alphas = lattice_grid(guesses,
                                    [alpha] if alphas is None else alphas)
        keys = jax.random.split(key, opts.shape[0])

    with TraceAnnotation("repro.dash.lattice"):
        results = _lattice_runner(obj, cfg)(obj, keys, opts, alphas)
    with TraceAnnotation("repro.dash.best"):
        best = _best_of_lattice(results)
    if return_lattice:
        return best, results
    return best
