"""Compiled bucket executors: many requests, one launch.

The request axis is PR 4's guess axis wearing a different hat: the
folded lattice machinery already vmaps ``dash`` over a leading
``(key, opt, alpha)`` axis under one compilation, with the filter-engine
``custom_vmap`` rules collapsing every lane's Monte-Carlo sweep into a
single fused launch.  A bucket of B requests against one dataset is
exactly that fold — per-lane keys and per-lane (OPT, α) guesses — so
the batcher reuses ``make_round_body``/``initial_carry`` verbatim and
adds only the serve-layer calling convention:

* dataset arrays are jit ARGUMENTS (stale-constant safety across warm
  cache updates — see ``serve.cache``), with the objective rebuilt
  inside the trace by the entry's factory;
* dash buckets are stepped ROUND-BY-ROUND from the host
  (:class:`DashBucket` — init/step/finalize) so the server can snapshot
  every boundary for hedged resume, enforce deadlines between rounds,
  and inject chaos deterministically; ``rho`` is a traced input, so ONE
  ``step`` compilation serves every round of every B-lane bucket;
* deterministic tiers (``topk``) run once and broadcast — their lanes
  are provably identical — while ``stochastic_greedy`` and ``fast`` vmap
  over lane keys; all are single-shot launches behind the same hedging
  wrapper.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.baselines import top_k_select
from repro.core.dash import _single_device_hooks
from repro.core.fast import fast
from repro.core.greedy import stochastic_greedy
from repro.core.selection_loop import (
    DashConfig,
    initial_carry,
    make_round_body,
)


class BatchOutput(NamedTuple):
    """Per-lane results of one bucket launch (leading axis = lane)."""

    sel_mask: jnp.ndarray    # (B, n) bool
    sel_count: jnp.ndarray   # (B,) int32
    value: jnp.ndarray       # (B,) f32


class DashBucket(NamedTuple):
    """Host-steppable compiled dash bucket.

    ``init(arrays, keys) -> carry`` builds the B-lane round-0 carry;
    ``step(arrays, rho, carry, opts, alphas) -> carry`` advances all
    lanes one round (the hedge/snapshot/deadline boundary);
    ``finalize(arrays, carry) -> BatchOutput`` reads out the results.
    """

    init: Callable
    step: Callable
    finalize: Callable
    cfg: DashConfig          # resolved — cfg.r is the step count


def build_dash_bucket(factory: Callable[[dict], Any],
                      cfg: DashConfig) -> DashBucket:
    """Compile the three dash-bucket entry points for a RESOLVED config.
    Lane count is implied by the ``keys`` argument, so one build serves
    every padded batch size (jit specializes per shape on first use)."""

    @jax.jit
    def init(arrays, keys):
        obj = factory(arrays)
        return jax.vmap(
            lambda kk: initial_carry(cfg, kk, obj.init(),
                                     jnp.ones((obj.n,), bool))
        )(keys)

    @jax.jit
    def step(arrays, rho, carry, opts, alphas):
        obj = factory(arrays)
        body = make_round_body(_single_device_hooks(obj, cfg), cfg)
        return jax.vmap(
            lambda c, g, a: body(rho, c, g, a)
        )(carry, opts, alphas)

    @jax.jit
    def finalize(arrays, carry):
        obj = factory(arrays)
        state = carry.state
        return BatchOutput(
            sel_mask=state.sel_mask,
            sel_count=carry.count,
            value=jax.vmap(obj.value)(state),
        )

    return DashBucket(init=init, step=step, finalize=finalize, cfg=cfg)


def build_single_shot(factory: Callable[[dict], Any], tier: str,
                      k: int, **opts) -> Callable:
    """One-launch executor ``run(arrays, keys) -> BatchOutput`` for every
    tier but dash."""
    keyed = {"stochastic_greedy": stochastic_greedy, "fast": fast}
    if tier in keyed:
        algo = keyed[tier]

        @jax.jit
        def run(arrays, keys):
            obj = factory(arrays)
            res = jax.vmap(lambda kk: algo(obj, k, kk, **opts))(keys)
            return BatchOutput(
                sel_mask=res.sel_mask,
                sel_count=jnp.sum(res.sel_mask.astype(jnp.int32), axis=-1),
                value=res.value,
            )

        return run

    if tier == "topk":

        @jax.jit
        def run(arrays, keys):
            # Deterministic: every lane would compute the identical set,
            # so run once and broadcast across the lane axis.
            obj = factory(arrays)
            res = top_k_select(obj, k)
            B = keys.shape[0]
            return BatchOutput(
                sel_mask=jnp.broadcast_to(res.sel_mask,
                                          (B,) + res.sel_mask.shape),
                sel_count=jnp.broadcast_to(res.sel_count, (B,)),
                value=jnp.broadcast_to(res.value, (B,)),
            )

        return run

    raise ValueError(f"no single-shot executor for tier {tier!r}")


def build_opt_probe(factory: Callable[[dict], Any], k: int) -> Callable:
    """``probe(arrays) -> ()`` top-k objective value — the cheap lower
    bound the server scales by its opt_margin to get dash's OPT guess
    (the ``data.selection.BatchSelector`` recipe, cached per (dataset,
    k) and invalidated on warm updates)."""

    @jax.jit
    def probe(arrays):
        obj = factory(arrays)
        return top_k_select(obj, k).value

    return probe


__all__ = ["BatchOutput", "DashBucket", "build_dash_bucket",
           "build_single_shot", "build_opt_probe"]
