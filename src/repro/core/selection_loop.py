"""The DASH round/filter control flow, shared by every runtime.

Paper Algorithm 1 (Thm 10) has one control structure — r outer rounds,
each running the threshold filter until the sampled-set gain clears
α²·t/r, then committing a uniformly sampled block — and it is the SAME
structure whether the oracle sweep runs on one device (``core.dash``) or
sharded over a mesh (``core.distributed``).  This module owns that
structure once: the runtimes supply a :class:`SelectionHooks` bundle
(how to estimate the two Monte-Carlo statistics, how to sample-and-commit
a block, how to count survivors) and :func:`run_selection_rounds` drives
the rounds, the Lemma-21-capped inner while loop, and the trace
bookkeeping.

Everything here is pure ``lax`` control flow: the loop jit/vmaps for the
OPT-guess lattice and runs unchanged inside ``shard_map`` (the hooks are
where collectives live — e.g. the distributed runtime's ``count_alive``
is a ``psum``, its estimators ``pmean`` over the data axis).

Per round (t = (1−ε)(OPT − f(S)), block b = ⌈k/r⌉):

    est ← Ê_{R~U(X)}[f_S(R)]
    while est < α²·t/r and iterations < ⌈log_{1+ε/2} n⌉ and |X| > 0:
        X ← X \\ { a : Ê_R[f_{S∪R}(a)] < α(1+ε/2)·t/k }       (filter)
        est ← Ê_{R~U(X)}[f_S(R)]
    S ← S ∪ R,  R ~ U(X)                                      (commit)

The iteration cap keeps the compiled while loop total even for
non-differentially-submodular inputs (paper App. A.2's failure mode).

Resilience (docs/resilience.md): the round boundary is the natural
snapshot point — the full loop state is one :class:`SelectionCarry`
pytree, and one round is a pure function of ``(carry, round, OPT, α)``.
:func:`make_round_body` exposes that per-round function so a host driver
(:func:`drive_checkpointed_rounds`) can step rounds one compiled call at
a time, snapshotting the carry through ``ckpt/checkpoint.py`` after each
boundary (:class:`RoundCheckpointer`, atomic + async) and regenerating
the straggler simulator's per-round responder masks
(``runtime/straggler.py::simulate_arrivals``) as a pure function of
``(seed, round)`` — which together make kill-and-resume replay exact.
"""

from __future__ import annotations

import math
import time
from dataclasses import dataclass
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

Array = Any

_RUNNER_CACHE_ATTR = "_selection_runner_cache"
# Fallback for objectives that cannot take new attributes (__slots__):
# entries here DO pin the objective until eviction, hence the small bound.
_RUNNER_CACHE_FALLBACK: dict = {}
_RUNNER_CACHE_FALLBACK_MAX = 16


def cached_runner(obj, key, build: Callable[[], Any]):
    """Per-objective cache for jitted selection-loop executors.

    Both runtimes build their jitted runners per (objective, config,
    layout); rebuilding per call would retrace and recompile every
    invocation, while a global ``lru_cache`` keyed on the objective
    would strongly pin each dead objective's device-resident dataset
    (X, y, caches) until enough entries accumulate.  The cache therefore
    lives ON the objective, so the GC frees runners and executables
    together with the objective.  Single-device runners take the
    objective (a pytree — ``objectives.base.PytreeObject``) as a jit
    ARGUMENT, so its arrays are program parameters and never constants
    baked into the executable.  ``key`` is any hashable residual
    (config, mesh, axes, flags).
    """
    try:
        per_obj = obj.__dict__.setdefault(_RUNNER_CACHE_ATTR, {})
    except AttributeError:       # __slots__ objective: bounded global dict
        per_obj = _RUNNER_CACHE_FALLBACK.setdefault(id(obj), (obj, {}))[1]
        while len(_RUNNER_CACHE_FALLBACK) > _RUNNER_CACHE_FALLBACK_MAX:
            _RUNNER_CACHE_FALLBACK.pop(next(iter(_RUNNER_CACHE_FALLBACK)))
    if key not in per_obj:
        per_obj[key] = build()
    return per_obj[key]


class DashTrace(NamedTuple):
    values: jnp.ndarray        # (r,) f(S) after each round
    alive: jnp.ndarray         # (r,) surviving |X| after each round
    filter_iters: jnp.ndarray  # (r,) inner-loop iterations used
    est_set_gain: jnp.ndarray  # (r,) final Ê[f_S(R)] per round


class SelectionCarry(NamedTuple):
    """The complete between-round loop state — ALSO the snapshot format.

    Everything a resumed run needs is here: the runtime's opaque oracle
    ``state`` (distributed: the replicated dist-state + selection mask),
    the survivor mask, |S|, the threaded PRNG key, and the trace.  A
    NamedTuple so it unpacks like the historical 5-tuple AND flattens to
    a stable pytree for ``ckpt/checkpoint.py``.
    """

    state: Any
    alive: Array
    count: Array
    key: Array
    trace: DashTrace


@dataclass(frozen=True)
class DashConfig:
    k: int                     # cardinality constraint
    r: int = 0                 # outer rounds (0 → ⌈log2 n⌉, clipped to k)
    eps: float = 0.2
    alpha: float = 0.5         # differential-submodularity parameter guess
    n_samples: int = 8         # Monte-Carlo sets per estimate (paper used 5)
    trim_frac: float = 0.0     # straggler/outlier trimming per side
    max_filter_iters: int = 0  # 0 → ⌈log_{1+ε/2} n⌉ (Lemma 21 cap)

    def resolve(self, n: int) -> "DashConfig":
        r = self.r or max(1, min(self.k, int(math.ceil(math.log2(max(n, 2))))))
        cap = self.max_filter_iters or (
            int(math.ceil(math.log(max(n, 2)) / math.log1p(self.eps / 2.0))) + 1
        )
        return DashConfig(
            k=self.k, r=r, eps=self.eps, alpha=self.alpha,
            n_samples=self.n_samples, trim_frac=self.trim_frac,
            max_filter_iters=cap,
        )

    @property
    def block(self) -> int:
        """⌈k/r⌉ — elements committed per outer round (resolved cfg only)."""
        return max(1, -(-self.k // max(self.r, 1)))


@dataclass(frozen=True)
class ResilienceConfig:
    """How a selection run snapshots, resumes and rides out stragglers.

    Checkpointing: with ``ckpt_dir`` set, the host-stepped drivers save
    the :class:`SelectionCarry` through ``ckpt/checkpoint.py`` every
    ``every`` completed rounds (atomic rename; ``async_save`` hands the
    write to a background thread so the device keeps stepping), pruning
    to the ``keep_last`` newest complete snapshots.

    Straggler simulation: ``drop_rate > 0`` makes each round's
    Monte-Carlo replica fleet miss the deadline independently with that
    probability (mask from ``runtime/straggler.py::simulate_arrivals``,
    a pure function of ``(straggler_seed, round)`` so interrupted and
    resumed runs see identical arrivals).  ``policy`` (a
    ``StragglerPolicy``; default constructed when None) sets the
    robust reduction for incomplete rounds — complete rounds
    short-circuit to the plain mean and stay bitwise deterministic.
    """

    ckpt_dir: str | None = None
    every: int = 1
    keep_last: int = 3
    async_save: bool = True
    drop_rate: float = 0.0
    straggler_seed: int = 0
    min_arrived: int = 1
    policy: Any = None

    @property
    def straggler(self) -> bool:
        return self.drop_rate > 0.0

    def resolved_policy(self):
        if self.policy is not None:
            return self.policy
        from repro.runtime.straggler import StragglerPolicy

        return StragglerPolicy()


class Deadline:
    """A monotonic wall-clock budget for a host-stepped selection run.

    ``clock`` is injectable (tests pass a counter) — the budget starts
    when the instance is constructed.  Shared by
    :func:`drive_checkpointed_rounds` and the selection server's drain
    path, so 'how long may this keep running' is answered one way
    everywhere.
    """

    def __init__(self, budget_s: float,
                 clock: Callable[[], float] = time.monotonic):
        self.budget_s = float(budget_s)
        self.clock = clock
        self.t0 = clock()

    def elapsed(self) -> float:
        return self.clock() - self.t0

    def remaining(self) -> float:
        return self.budget_s - self.elapsed()

    def expired(self) -> bool:
        return self.remaining() <= 0.0


class SelectionDeadlineExceeded(RuntimeError):
    """A host-stepped selection run ran out of deadline budget.

    Carries how many rounds completed and (when the driver has one) the
    partial :class:`SelectionCarry`, so a serving layer can degrade or
    reject explicitly instead of hanging.  Retrying cannot help, so the
    resilience wrappers treat it as fatal (``fatal=`` in
    ``run_with_restart`` / ``run_resumable``).
    """

    def __init__(self, rounds_done: int, carry: Any = None):
        super().__init__(
            f"selection deadline expired after {int(rounds_done)} "
            f"completed rounds"
        )
        self.rounds_done = int(rounds_done)
        self.carry = carry


class RoundCheckpointer:
    """Async round-boundary snapshot writer over ``ckpt/checkpoint.py``.

    ``save`` fetches the carry to host synchronously (the only bubble
    the device sees) and, in async mode, writes/prunes on a background
    thread — one write in flight at a time, errors surfaced on the next
    ``save``/``wait``.  The atomic tmp→rename in ``save_checkpoint``
    means a kill at ANY point leaves the newest complete snapshot
    restorable.
    """

    def __init__(self, cfg: ResilienceConfig):
        if not cfg.ckpt_dir:
            raise ValueError("RoundCheckpointer needs ResilienceConfig.ckpt_dir")
        self.cfg = cfg
        self._thread = None
        self._error: Exception | None = None

    def save(self, rounds_done: int, carry, *, extra: dict | None = None,
             blocking: bool = False):
        from repro.ckpt.checkpoint import save_checkpoint

        self.wait()
        host = jax.tree_util.tree_map(np.asarray, jax.device_get(carry))
        meta = dict(extra or {})
        meta["round"] = int(rounds_done)

        def work():
            try:
                save_checkpoint(self.cfg.ckpt_dir, rounds_done, host,
                                extra=meta, keep_last=self.cfg.keep_last)
            except Exception as e:     # surfaced on next save/wait
                self._error = e

        if self.cfg.async_save and not blocking:
            import threading

            self._thread = threading.Thread(target=work, daemon=True)
            self._thread.start()
        else:
            work()
            self.wait()

    def wait(self, *, raise_errors: bool = True):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error and raise_errors:
            err, self._error = self._error, None
            raise err


def _count_alive_local(alive) -> Array:
    return jnp.sum(alive.astype(jnp.int32))


@dataclass(frozen=True)
class SelectionHooks:
    """Oracle bundle binding the shared loop to a runtime.

    ``state`` is opaque to the loop — any pytree the hooks agree on (the
    single-device runtime passes the objective's state; the distributed
    runtime passes ``(replicated oracle state, shard-local sel mask)``).
    ``alive`` is the (possibly shard-local) bool survivor mask the loop
    threads through the filter.

    Hooks and their contracts:
      value(state) -> f(S)                                (replicated)
      sel_mask(state) -> bool mask aligned with ``alive``
      estimate_set_gain(state, alive, allowed, key) -> Ê_{R~U(X)}[f_S(R)]
      estimate_elem_gains(state, alive, allowed, key)
          -> per-candidate Ê_R[f_{S∪R}(a)], aligned with ``alive``
      pick_and_add(state, alive, allowed, key) -> (state, #added)
      count_alive(alive) -> GLOBAL survivor count (distributed: psum)

    ``allowed`` is the remaining capacity k − |S| (clamps sample slots so
    a round at the capacity edge cannot overfill the solution).
    """

    value: Callable[[Any], Array]
    sel_mask: Callable[[Any], Array]
    estimate_set_gain: Callable[[Any, Array, Array, Array], Array]
    estimate_elem_gains: Callable[[Any, Array, Array, Array], Array]
    pick_and_add: Callable[[Any, Array, Array, Array], tuple]
    count_alive: Callable[[Array], Array] = _count_alive_local


def initial_carry(cfg: DashConfig, key, state0: Any,
                  alive0: Array) -> SelectionCarry:
    """Round-0 carry for a ``resolve``-d config (zeroed trace/count)."""
    r = cfg.r
    trace0 = DashTrace(
        values=jnp.zeros((r,)), alive=jnp.zeros((r,), jnp.int32),
        filter_iters=jnp.zeros((r,), jnp.int32), est_set_gain=jnp.zeros((r,)),
    )
    return SelectionCarry(state=state0, alive=alive0,
                          count=jnp.zeros((), jnp.int32), key=key,
                          trace=trace0)


def make_round_body(hooks: SelectionHooks, cfg: DashConfig):
    """One DASH round as a pure function — the unit both drivers step.

    Returns ``round_body(rho, carry, opt, alpha) -> SelectionCarry``
    with every argument traced: :func:`run_selection_rounds` folds it
    into a ``fori_loop``, while the checkpointed drivers jit it once
    (``rho``/``opt``/``alpha`` as runtime inputs) and call it per round
    from the host — ONE compilation serves every round of every guess.
    """
    k, r = cfg.k, cfg.r

    def round_body(rho, carry: SelectionCarry, opt, alpha) -> SelectionCarry:
        with jax.named_scope("repro.round"):
            return _round(rho, carry, opt, alpha)

    def _round(rho, carry: SelectionCarry, opt, alpha) -> SelectionCarry:
        state, alive, count, key, trace = carry
        alpha = jnp.asarray(alpha, jnp.float32)
        alpha2 = alpha * alpha
        opt = jnp.asarray(opt, jnp.float32)
        key, k_est, k_pick = jax.random.split(key, 3)
        value = hooks.value(state)
        t = jnp.maximum((1.0 - cfg.eps) * (opt - value), 0.0)
        thr_set = alpha2 * t / r
        thr_elem = alpha * (1.0 + cfg.eps / 2.0) * t / k
        allowed = jnp.maximum(k - count, 0)

        with jax.named_scope("repro.estimate"):
            est0 = hooks.estimate_set_gain(state, alive, allowed, k_est)

        def cond(w):
            alive_w, key_w, est_w, it = w
            return (
                (est_w < thr_set)
                & (it < cfg.max_filter_iters)
                & (hooks.count_alive(alive_w) > 0)
            )

        def body(w):
            alive_w, key_w, est_w, it = w
            key_w, k_f, k_e = jax.random.split(key_w, 3)
            eg = hooks.estimate_elem_gains(state, alive_w, allowed, k_f)
            alive_w = alive_w & (eg >= thr_elem) & ~hooks.sel_mask(state)
            with jax.named_scope("repro.estimate"):
                est_w = hooks.estimate_set_gain(state, alive_w, allowed, k_e)
            return alive_w, key_w, est_w, it + 1

        with jax.named_scope("repro.filter"):
            alive, key, est, iters = jax.lax.while_loop(
                cond, body, (alive, key, est0, jnp.zeros((), jnp.int32))
            )

        state, added = hooks.pick_and_add(state, alive, allowed, k_pick)
        alive = alive & ~hooks.sel_mask(state)
        trace = DashTrace(
            values=trace.values.at[rho].set(hooks.value(state)),
            alive=trace.alive.at[rho].set(hooks.count_alive(alive)),
            filter_iters=trace.filter_iters.at[rho].set(iters),
            est_set_gain=trace.est_set_gain.at[rho].set(est),
        )
        return SelectionCarry(state=state, alive=alive, count=count + added,
                              key=key, trace=trace)

    return round_body


def run_selection_rounds(
    hooks: SelectionHooks,
    cfg: DashConfig,
    opt: Array,
    key: Array,
    state0: Any,
    alive0: Array,
    alpha: Array | None = None,
) -> SelectionCarry:
    """Drive the r DASH rounds.  ``cfg`` must already be ``resolve``-d.

    ``alpha`` optionally overrides ``cfg.alpha`` with a *traced* value —
    this is what lets the OPT-guess lattice vmap over (OPT, α) pairs
    under ONE compilation instead of retracing per α.

    Returns the final :class:`SelectionCarry` (unpacks like the
    historical ``(state, alive, count, key, trace)`` tuple).
    """
    alpha = jnp.asarray(cfg.alpha if alpha is None else alpha, jnp.float32)
    opt = jnp.asarray(opt, jnp.float32)
    body = make_round_body(hooks, cfg)
    return jax.lax.fori_loop(
        0, cfg.r, lambda rho, c: body(rho, c, opt, alpha),
        initial_carry(cfg, key, state0, alive0),
    )


def round_arrivals(resilience: ResilienceConfig | None, cfg: DashConfig,
                   rho: int) -> np.ndarray:
    """The round's (n_samples,) responder mask — all-ones unless the
    resilience config simulates deadline misses.  Pure in (config, ρ)."""
    if resilience is not None and resilience.straggler:
        from repro.runtime.straggler import simulate_arrivals

        return simulate_arrivals(
            resilience.straggler_seed, rho, cfg.n_samples,
            resilience.drop_rate, min_arrived=resilience.min_arrived,
        )
    return np.ones((cfg.n_samples,), bool)


def drive_checkpointed_rounds(
    step_fn: Callable[[int, SelectionCarry, np.ndarray], SelectionCarry],
    carry: SelectionCarry,
    cfg: DashConfig,
    *,
    resilience: ResilienceConfig | None = None,
    start_round: int = 0,
    failure_injector=None,
    snapshot_extra: dict | None = None,
    deadline: Deadline | None = None,
) -> SelectionCarry:
    """Host-driven round loop with snapshots — the resilient twin of
    :func:`run_selection_rounds`.

    ``step_fn(rho, carry, arrived)`` is one compiled round (the runtimes
    build it from :func:`make_round_body`); ``carry`` between calls is a
    HOST-visible global view, which is exactly what gets snapshotted —
    and why a snapshot taken on one mesh restores onto another.
    ``failure_injector.check(rho)`` runs before each round, so an
    injected kill loses at most the rounds since the last snapshot.
    ``deadline`` bounds the host loop: an expired budget raises
    :class:`SelectionDeadlineExceeded` (with the partial carry attached)
    at the next round boundary instead of letting the run spin past its
    budget — the serving layer's degradation/rejection hook.
    """
    ckpt = (RoundCheckpointer(resilience)
            if resilience is not None and resilience.ckpt_dir else None)
    try:
        for rho in range(start_round, cfg.r):
            if deadline is not None and deadline.expired():
                raise SelectionDeadlineExceeded(rho, carry)
            if failure_injector is not None:
                failure_injector.check(rho)
            arrived = round_arrivals(resilience, cfg, rho)
            carry = step_fn(rho, carry, arrived)
            if ckpt is not None and (rho + 1) % resilience.every == 0:
                ckpt.save(rho + 1, carry, extra=snapshot_extra)
    finally:
        if ckpt is not None:
            # Let an in-flight write land (so an injected failure's
            # restore sees a deterministic newest snapshot) without
            # masking the propagating exception with a writer error.
            ckpt.wait(raise_errors=False)
    if ckpt is not None:
        ckpt.wait()
    return carry
