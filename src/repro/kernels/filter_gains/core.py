"""Shared tiling/launch core for the sample-batched filter engine.

Every filter-engine kernel evaluates, for all ``n_samples`` Monte-Carlo
perturbed states S ∪ R_i at once, a per-candidate statistic over the
ground-set matrix X.  The launch geometry is always the same:

    grid = (n // block_n, n_guesses * n_samples)   # sample axis MINOR

so for a fixed candidate block the (guess, sample) index varies fastest
and the streamed (d, block_n) operands stay resident in VMEM across all
samples of all guesses — each X block is fetched from HBM once per
launch instead of once per sample (or once per OPT guess).  What differs
between objectives is only the *epilogue*: the per-block math that turns
the shared operands and the current sample's operands into gains (see
``kernel.py`` / ``kernel_aopt.py`` / ``kernel_logistic.py``).

The guess axis (the DASH (OPT, α) lattice, paper App. G) is FOLDED into
the sample grid axis: grid position ``s`` on the minor axis means guess
``s // n_samples``, sample ``s % n_samples``.  Guess-dependent state
operands carry a leading ``n_guesses`` axis and are indexed off the
program id by the ``g*`` operand kinds below, so one compiled launch
sweeps the whole lattice instead of ``n_guesses`` separate launches
re-streaming X each time.

This module owns the geometry so an epilogue author only declares what
each operand *is*; the seven operand kinds are:

  ``stream``  (d, n)      candidate-blocked, constant over samples AND
                          guesses — the big matrices whose HBM traffic
                          the engine amortizes (X).
  ``gstream`` (G, d, n)   candidate-blocked, one (d, n) slab per guess
                          (A-optimality's shared solve W = M⁻¹X depends
                          on the guess's state); re-fetched only at
                          guess boundaries thanks to sample-minor order.
  ``const``   any shape   fetched once (constant index map): operands
                          shared by every guess (the labels y).
  ``gconst``  (G, *rest)  per-guess shared state, fetched once per guess
                          (the regression basis Q).
  ``sample``  (G·m, *rest) blocked over the folded sample grid axis: one
                          slice per (guess, sample) perturbed state
                          (delta bases, residuals, per-sample logits).
  ``cand``    (n,)        per-candidate vectors, reshaped to (1, n) and
                          blocked with the candidate axis (‖x_a‖², …).
  ``gcand``   (G, n)      per-guess per-candidate rows (A-optimality's
                          ‖w_a‖², x_aᵀw_a — functions of the guess's W).

The output is always (G·m, n) f32; the epilogue writes one (1, block_n)
row per grid step.  The TPU lowering requires the last two block axes to
be (8, 128)-aligned or to span the whole array, so one-row blocks of 2-D
``sample`` / ``gcand`` operands and of the output are taken from a
(rows, 1, cols) view with the leading axis squeezed out: the epilogue
still sees (1, ·) refs.
Grid dimensions are sequential ("arbitrary") by default on TPU, which is
what lets an epilogue cache sample-independent work in VMEM scratch at
guess boundaries (``pl.program_id(1) % n_samples == 0``) and reuse it
for the guess's remaining samples (the regression epilogue does this for
its shared-base projection).

Block sizes and padding are the *callers'* job (ops.py via
``repro.kernels.common``): operands arriving here must already be padded
so that n % block_n == 0 and the feature/basis axes meet f32 sublane
tiling.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


class Operand(NamedTuple):
    """One engine operand: the array plus its blocking kind."""

    array: Any
    kind: str  # "stream" | "gstream" | "const" | "gconst"
    #          # | "sample" | "cand" | "gcand"


def _spec_for(arr, kind: str, block_n: int, m: int) -> pl.BlockSpec:
    """BlockSpec for one operand; ``m`` is n_samples PER GUESS (the
    guess of minor grid position s is ``s // m``)."""
    if kind == "stream":
        d = arr.shape[0]
        return pl.BlockSpec((d, block_n), lambda i, s: (0, i))
    if kind == "gstream":
        d = arr.shape[1]
        return pl.BlockSpec(
            (1, d, block_n), lambda i, s, _m=m: (s // _m, 0, i)
        )
    if kind == "const":
        nd = arr.ndim
        return pl.BlockSpec(arr.shape, lambda i, s, _nd=nd: (0,) * _nd)
    if kind == "gconst":
        rest = arr.shape[1:]
        nr = len(rest)
        return pl.BlockSpec(
            (1, *rest), lambda i, s, _nr=nr, _m=m: (s // _m,) + (0,) * _nr
        )
    if kind == "sample" and arr.ndim == 3:
        rest = arr.shape[1:]
        return pl.BlockSpec((1, *rest), lambda i, s: (s, 0, 0))
    if kind == "sample":
        # 2-D (G·m, r) operands are launched as (G·m, 1, r): a one-row
        # block must span the whole of the last two axes on TPU.
        r = arr.shape[1]
        return pl.BlockSpec((None, 1, r), lambda i, s: (s, 0, 0))
    if kind == "cand":
        return pl.BlockSpec((1, block_n), lambda i, s: (0, i))
    if kind == "gcand":
        return pl.BlockSpec(
            (None, 1, block_n), lambda i, s, _m=m: (s // _m, 0, i)
        )
    raise ValueError(f"unknown operand kind: {kind!r}")


def launch_filter_engine(
    body,
    operands: Sequence[Operand],
    *,
    name: str,
    n: int,
    n_samples: int,
    block_n: int,
    n_guesses: int = 1,
    scratch_shapes: Sequence[Any] = (),
    interpret: bool = False,
):
    """Launch a filter-engine epilogue over the (candidate, guess·sample)
    grid.

    ``body(*in_refs, o_ref, *scratch_refs)`` receives one ref per operand
    (in order), the (1, block_n) output ref, then the scratch refs.  The
    folded minor grid position is ``pl.program_id(1)`` — guess
    ``s // n_samples``, sample ``s % n_samples``; candidate block is
    axis 0.  ``sample`` operands must arrive FOLDED: leading axis
    ``n_guesses * n_samples``, guess-major.  ``cand`` operands must be
    passed 1-D; they are reshaped to (1, n) here so the epilogue always
    sees (1, block_n) refs (``gcand`` operands are (G, n)).  ``name``
    is the launch's name in the compiled program and the profile (the
    calling wrapper's).  Returns (n_guesses·n_samples, n) — callers
    unfold.
    """
    assert n % block_n == 0, (n, block_n)
    arrays = []
    in_specs = []
    for arr, kind in operands:
        if kind == "sample":
            assert arr.shape[0] == n_guesses * n_samples, (
                arr.shape, n_guesses, n_samples
            )
            assert arr.ndim in (2, 3), arr.shape
        if kind in ("gstream", "gconst", "gcand"):
            assert arr.shape[0] == n_guesses, (arr.shape, n_guesses)
        in_specs.append(_spec_for(arr, kind, block_n, n_samples))
        if kind == "cand":
            arr = arr[None, :]
        elif arr.ndim == 2 and kind in ("sample", "gcand"):
            arr = arr[:, None, :]
        arrays.append(arr)
    total = n_guesses * n_samples
    out = pl.pallas_call(
        body,
        grid=(n // block_n, total),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, 1, block_n), lambda i, s: (s, 0, i)),
        out_shape=jax.ShapeDtypeStruct((total, 1, n), jnp.float32),
        scratch_shapes=list(scratch_shapes),
        interpret=interpret,
        name=name,
    )(*arrays)
    return out.reshape(total, n)
