"""FAST — adaptive sequencing + binary-search thresholding.

The paper (§1.2) notes differential submodularity extends beyond
adaptive *sampling* to adaptive-*sequencing*-style techniques; this
module implements Breuer, Balkanski & Singer's FAST ("The FAST
Algorithm for Submodular Maximization") as a first-class registry
algorithm — the ROADMAP's low-adaptivity frontier.

Structure (one jitted launch per run):

  * **Outer loop — binary-searched OPT guess.**  The same geometric
    guess lattice DASH sweeps (``core.dash.opt_guess_lattice``,
    spanning [max_a f(a), k·max_a f(a)]) is *binary searched* instead of
    exhaustively swept: a guess is feasible when the inner run attains
    ``(1 − 1/e)(1 − ε)`` of it, and ⌈log₂ G⌉ probes find the largest
    feasible guess.  The search is IN-GRAPH (``jnp.where`` carries the
    running best), so the whole thing stays one compiled launch —
    jittable, vmappable (``select_batched``), and shard_map-safe for the
    distributed twin.

  * **Threshold ladder.**  Per guess, thresholds decay geometrically
    from the TOP of the actual gain range (``max_a f(a)`` — FAST's
    descending threshold grid) down to the guess-dependent floor
    ``ε·opt/k`` (elements below the floor contribute < ε·OPT in
    total): a round that commits nothing steps the ladder
    ``t ← (1 − ε)·t`` and re-filters the alive set.

  * **Inner adaptive-sequencing rounds.**  Draw a uniformly random
    sequence (a_1, …, a_L) from the alive set (Gumbel-top-k — the SAME
    replicated noise layout every sampler in this codebase uses, which
    is what buys the distributed twin bitwise parity), evaluate the
    gain of every element at its insertion prefix, commit the longest
    prefix every element of which — its tail included — cleared the
    threshold at its insertion point, and filter survivors by their
    gains at the committed state.

The perf move — prefixes ≈ samples
----------------------------------
A sequence's L insertion prefixes map onto the *sample axis* of the
fused filter engine: prefix j is the "Monte-Carlo sample"
R_j = {a_1, …, a_j}, encoded as ``idx = seq`` (broadcast) with
``mask_j = arange(L) < j``.  One ``filter_gains_batch`` call of
``L + 1`` samples returns gains at EVERY insertion prefix (row j) and
at the post-commit state (row c) in a single fused kernel launch —
reusing ``repro.kernels.filter_gains`` (including ``precision=``
streaming and the autotuned-block cache) instead of growing a new
kernel.  This replaces the sequential L-step ``set_gain`` scan of the
original ``core.adaptive_sequencing`` (which that module now also
routes through :func:`sequence_prefix_gains`).

Compared to lazy greedy (the strong practical competitor), FAST trades
k sequential host-driven picks for a handful of fused device rounds, to
win wall-clock at matched objective value; no chip run has measured
that comparison yet.

See docs/fast.md for the full semantics and the distributed twin's
collectives table (``core.distributed.fast_distributed``).
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.estimators import sample_set_from_mask
from repro.core.objectives.base import with_precision
from repro.core.selection_loop import cached_runner


class FastResult(NamedTuple):
    sel_mask: jnp.ndarray   # (n,) bool
    sel_count: jnp.ndarray  # () int32
    value: jnp.ndarray      # () f32 — f(S)
    rounds: jnp.ndarray     # () int32 — adaptive rounds consumed
    values: jnp.ndarray     # (r_max,) per-round f(S) trace (0-padded)
    opt: jnp.ndarray        # () f32 — the (binary-searched) OPT guess used


#: Feasibility fraction for the OPT binary search: a guess g survives
#: when the inner run attains (1 − 1/e)(1 − ε)·g.  Differential
#: submodularity weakens the constant by α², so infeasible-looking
#: guesses are common on the paper's objectives — the search also
#: carries the running best-value result, which makes the final answer
#: monotone in probe count rather than hostage to the constant.
_FEASIBLE_FRAC = 1.0 - 1.0 / math.e


def ladder_levels(k: int, eps: float) -> int:
    """Number of geometric decays from the ladder's start (the top
    singleton gain) to the ``ε·opt/k`` floor.  The worst-case span is a
    factor of k/ε (opt is at most k times the top singleton gain):
    ⌈ln(k/ε) / −ln(1−ε)⌉ (≈ 17 at ε = 0.2, k = 8)."""
    return int(math.ceil(
        math.log(max(int(k), 1) / eps) / -math.log(1.0 - eps)))


def fast_round_cap(k: int, eps: float) -> int:
    """Static while-loop bound: every round either commits ≥ 1 element
    (≤ k such rounds) or steps the ladder (≤ ``ladder_levels`` such
    rounds); +2 slack for the entry/exit rounds."""
    return int(k) + ladder_levels(k, eps) + 2


def _resolve_engine(obj, use_filter_engine) -> bool:
    if use_filter_engine is None:
        use_filter_engine = bool(getattr(obj, "use_filter_engine", False))
    return use_filter_engine and hasattr(obj, "filter_gains_batch")


def q_cmp(x):
    """bf16 view of a comparison operand.

    Every threshold DECISION in fast (alive filters, the prefix-commit
    rule, binary-search feasibility) compares bf16-quantized values:
    the two runtimes compute gains through differently fused XLA
    programs (plain jit vs shard_map), whose f32 results can wobble in
    the last bit — on objectives with exactly clustered gains
    (normalized A-opt columns all open at 1/2) a knife-edge ``>=``
    would turn that wobble into runtime-dependent selections.  bf16's
    2⁻⁸ granularity is ~3 decades coarser than the wobble and ~1 decade
    finer than an ε-rung, so decisions become fusion-invariant while
    the ladder semantics are unchanged.  Values themselves stay f32 —
    only comparisons look through this view.
    """
    return x.astype(jnp.bfloat16)


def prefix_masks(L: int):
    """(L + 1, L) bool: row j marks the length-j insertion prefix —
    the prefixes-≈-samples encoding for the filter engine."""
    return jnp.arange(L)[None, :] < jnp.arange(L + 1)[:, None]


def sequence_prefix_gains(obj, state, seq_idx, slot_ok, *, engine: bool):
    """Gains at EVERY insertion prefix of a sequence, one fused launch.

    ``seq_idx`` (L,) int32 is the drawn sequence, ``slot_ok`` (L,) bool
    its slot validity.  Returns ``(G, marg)``:

      * ``G``    (L + 1, n): row j = gains w.r.t. S ∪ {a_1, …, a_j} —
        exactly ``vmap(lambda R_j: gains(add_set(state, R_j)))`` with
        R_j the length-j prefix, but evaluated as ONE
        ``filter_gains_batch`` call (prefix j rides the engine's sample
        axis).  Row L is the gains after inserting the whole sequence;
        row c is the post-commit filter sweep for a committed c-prefix.
      * ``marg`` (L,): the gain of element a_{j+1} *at its insertion
        point*, ``G[j, seq_idx[j]]`` — the quantity the prefix-commit
        rule compares against the threshold t.

    Objectives without the filter engine fall back to the per-prefix
    vmap (identical semantics, one ``add_set``+``gains`` per prefix).
    """
    L = seq_idx.shape[0]
    masks = prefix_masks(L) & slot_ok[None, :]
    if engine:
        idx_b = jnp.broadcast_to(seq_idx, (L + 1, L))
        G = obj.filter_gains_batch(state, idx_b, masks)
    else:
        G = jax.vmap(
            lambda m: obj.gains(obj.add_set(state, seq_idx, m))
        )(masks)
    marg = G[jnp.arange(L), seq_idx]
    return G, marg


def _make_fast_core(obj, k: int, eps: float, r_max: int, engine: bool):
    """The single-guess FAST body: ``run(key, opt) -> FastResult``.

    Pure traced function (while_loop inside); the binary search and the
    distributed twin both drive it.
    """
    n = obj.n
    L = min(int(k), int(n))
    ar = jnp.arange(L)

    def run(key, opt):
        opt = jnp.asarray(opt, jnp.float32)
        state0 = obj.init()
        g0 = obj.gains(state0)
        # Seed S with the argmax singleton — greedy's first pick, made
        # by index comparison rather than a threshold test.  The ladder
        # then starts one rung below the top of the ACTUAL gain range —
        # the i = 1 entry of FAST's descending grid
        # {(1−ε)^i · max_a f(a)} — and bottoms out at the
        # guess-dependent floor ε·opt/k: the OPT guess decides how deep
        # the ladder digs (elements below the floor contribute < ε·OPT
        # in total), not where it starts.  Both choices matter for
        # parity: a ladder opening AT the max asks round 1 to compare
        # the argmax's gain against ITSELF recomputed through the fused
        # prefix sweep, a bitwise knife-edge that objectives with
        # exactly tied singleton gains (normalized A-opt columns all
        # open at 1/2) turn into runtime-dependent selections — the
        # argmax seed keeps the top pick exact and the threshold tests
        # generic.
        a0 = jnp.argmax(q_cmp(g0))
        state0 = obj.add_set(state0, a0[None], jnp.ones((1,), bool))
        t0 = (1.0 - eps) * jnp.max(g0)
        t_min = eps * opt / k
        alive0 = (q_cmp(obj.gains(state0)) >= q_cmp(t0)) & ~state0.sel_mask

        def cond(c):
            _, _, t, count, _, rho, _ = c
            return (rho < r_max) & (count < k) & (t >= t_min)

        def body(c):
            state, alive, t, count, key, rho, values = c
            key, k_seq = jax.random.split(key)
            # Uniform random sequence from the alive set (Gumbel-top-k,
            # replicated noise layout — see _dist_sample for the twin).
            seq_idx, seq_valid = sample_set_from_mask(k_seq, alive, L)
            allowed = jnp.clip(k - count, 0, L)
            slot_ok = seq_valid & (ar < allowed)
            G, marg = sequence_prefix_gains(obj, state, seq_idx, slot_ok,
                                            engine=engine)
            # Longest prefix every element of which (its tail included)
            # cleared the threshold at its own insertion point — the
            # leading run of clears.  Every committed element is
            # individually certified ≥ t, so a low-t round can never
            # smuggle in sub-threshold middles.
            clear = slot_ok & (q_cmp(marg) >= q_cmp(t))
            c_len = jnp.sum(
                jnp.cumprod(clear.astype(jnp.int32))).astype(jnp.int32)
            commit = ar < c_len
            state = obj.add_set(state, seq_idx, commit)
            count = count + c_len
            # Empty round ⇒ the threshold outran the pool: ladder step.
            t = jnp.where(c_len > 0, t, (1.0 - eps) * t)
            # Row c of the SAME fused sweep is the post-commit filter.
            g_c = jnp.take(G, c_len, axis=0)
            alive = (q_cmp(g_c) >= q_cmp(t)) & ~state.sel_mask
            values = values.at[rho].set(obj.value(state))
            return state, alive, t, count, key, rho + 1, values

        state, _, _, count, _, rho, values = jax.lax.while_loop(
            cond, body,
            (state0, alive0, t0, jnp.ones((), jnp.int32), key,
             jnp.zeros((), jnp.int32), jnp.zeros((r_max,), jnp.float32)),
        )
        return FastResult(
            sel_mask=state.sel_mask, sel_count=count,
            value=obj.value(state), rounds=rho, values=values, opt=opt,
        )

    return run


def binary_search_opt(run_core, key, guesses, eps: float):
    """In-graph binary search of the OPT guess lattice.

    ``guesses`` (G,) ascending; ⌈log₂ G⌉ probes of ``run_core``, each on
    a key folded with the probe index.  A guess is feasible when its run
    attains ``_FEASIBLE_FRAC·(1 − ε)`` of it; the search walks toward
    the largest feasible guess while a ``jnp.where``-merged running best
    (NaN lanes can never win) is what is returned — all traced, so the
    whole search is one compiled program shared by both runtimes.
    """
    G = int(guesses.shape[0])
    steps = max(1, int(math.ceil(math.log2(G)))) if G > 1 else 1
    ratio = _FEASIBLE_FRAC * (1.0 - eps)

    lo = jnp.zeros((), jnp.int32)
    hi = jnp.full((), G - 1, jnp.int32)
    best = None
    for s in range(steps):
        mid = jnp.clip((lo + hi) // 2, 0, G - 1)
        g = jnp.take(guesses, mid)
        res = run_core(jax.random.fold_in(key, s), g)
        if best is None:
            best = res
        else:
            v_new = jnp.where(jnp.isnan(res.value), -jnp.inf, res.value)
            v_old = jnp.where(jnp.isnan(best.value), -jnp.inf, best.value)
            better = q_cmp(v_new) > q_cmp(v_old)
            best = jax.tree_util.tree_map(
                lambda a, b: jnp.where(better, a, b), res, best)
        feasible = q_cmp(res.value) >= q_cmp(ratio * g)
        lo = jnp.where(feasible, mid + 1, lo)
        hi = jnp.where(feasible, hi, mid - 1)
    return best


def fast(
    obj, k: int, key=None, *, eps: float = 0.06, opt=None,
    n_guesses: int = 8, max_rounds: int = 0,
    use_filter_engine: bool | None = None, precision: str | None = None,
) -> FastResult:
    """Run FAST on a single device.

    ``opt`` pins a single OPT guess (one ladder run — the mode the
    parity tests and ``select_batched`` callers use); omitting it binary
    searches the ``n_guesses``-point geometric lattice in-graph
    (⌈log₂ n_guesses⌉ full runs inside ONE compiled launch).
    ``max_rounds`` overrides the static round cap
    (:func:`fast_round_cap`).  ``use_filter_engine=None`` defers to
    ``obj.use_filter_engine`` — the engine path evaluates each round's
    L + 1 insertion prefixes as one fused ``filter_gains_batch`` launch.
    ``precision="bf16"`` streams the kernel operands in bf16 with f32
    accumulation (``with_precision`` view, exactly like ``select()``).

    Jitted runners are weak-cached per objective (``cached_runner``), so
    guess sweeps / benchmarks / repeated serving calls never retrace.
    """
    from repro.core.dash import opt_guess_lattice

    if precision is not None:
        obj = with_precision(obj, precision)
    k = int(k)
    if k <= 0:
        raise ValueError(f"k must be a positive integer, got {k!r}")
    if key is None:
        key = jax.random.PRNGKey(0)
    eps = float(eps)
    engine = _resolve_engine(obj, use_filter_engine)
    r_max = int(max_rounds) or fast_round_cap(k, eps)

    if opt is not None:
        guesses = jnp.asarray(opt, jnp.float32).reshape(1)
    else:
        guesses = opt_guess_lattice(obj, eps, n_guesses, k)
    G = int(guesses.shape[0])

    def run(o, kk, gg):
        core = _make_fast_core(o, k, eps, r_max, engine)
        return binary_search_opt(core, kk, gg, eps)

    runner = cached_runner(obj, ("fast", k, eps, r_max, engine, G),
                           lambda: jax.jit(run))
    return runner(obj, key, guesses)


def fast_cost(n: int, k: int, eps: float = 0.06) -> dict:
    """{"oracle_calls", "adaptive_rounds"} at FAST's leading order.

    Per probe the ladder has ``ladder_levels(k, eps)`` decay rounds plus
    O(log n) committing rounds (each commits an expected constant
    fraction of the remaining budget); the binary search multiplies by
    ⌈log₂ G⌉ probes.  Each round's fused prefix sweep touches every
    surviving candidate once per prefix — reported at the paper-style
    n-per-round leading order, like the DASH entry.
    """
    per_probe = ladder_levels(k, eps) + int(
        math.ceil(math.log2(max(min(n, k) + 1, 2))))
    probes = max(1, int(math.ceil(math.log2(8))))
    r = probes * per_probe
    return {"oracle_calls": n * r, "adaptive_rounds": r}
