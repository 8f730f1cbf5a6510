"""The comparison that decides ``correct``.

Once the window has closed, every selection the window produced is
fetched and judged against the configuration's plain reference
(``bench/references/<reference>.py``):

* ``invalid_calls``: calls that raised, or whose set is not valid:
  |S| ≠ k (traffic with ``size: "at_most"``, whose algorithm may stop
  short of k: |S| outside 1..k), or ``sel_count`` ≠ |S|.  The limit
  is 0;
* ``value_gap``: the widest relative gap between the f(S) the program
  reported and the reference's f(S) of the same set.

Then each Pallas kernel that the configuration names, and that the
cell's traffic launches (``harness.launch``), is compared at the cell's
own launch shape: ``<kernel>_gap`` is the largest |program − reference|
over the largest |reference|, over the candidates the program scores.
The program's side is the objective instance the window called, with
its own options (precision, kernel switch), driven through the methods
the selection loop calls: states S_g built by its ``add_set`` (at
``highest`` precision, so that the number is the launch's and not the
state's rounding, which ``value_gap`` covers), then, at the window's
default precision, ``filter_gains_batch`` at the (guess, sample)
lattice under ``vmap`` over guesses (the lattice's one folded launch),
or ``gains`` for a sweep.  The reference (``bench/kernel_checks/<kernel>.py``) scores the
same sets from the definition.

A control replaces the program's numbers by the reference's own,
computed in a lower precision (``harness.lowp``): the reported f(S) by
the lower-precision f(S), the kernel's output by the lower-precision
reference.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from harness import launch

CHECK_KEY_TAG = 0x6B65726E          # folded into the seed for kernel operands


def _value_fn(ref, params, lower):
    return jax.jit(lambda data, idx, size: ref.value(data, idx, size, params,
                                                     lower))


def selections(cell, data, calls, ref, lower=None):
    """(numbers, f_values): the selection numbers over ``calls`` and the
    reference's f(S) of each valid selection."""
    k = int(cell.sizes["k"])
    at_most = cell.traffic.get("size", "exact") == "at_most"
    params = cell.config["objective"].get("options", {})
    value = _value_fn(ref, params, None)
    control = _value_fn(ref, params, lower) if lower else None
    invalid, gaps, f_values = 0, [], []
    for c in calls:
        if c.out is None:
            invalid += 1
            continue
        idx = np.flatnonzero(np.asarray(c.out["sel_mask"]))
        size = len(idx)
        ok = (0 < size <= k) if at_most else size == k
        if not ok or int(c.out["sel_count"]) != size:
            invalid += 1
            continue
        pad = np.zeros((k,), np.int32)
        pad[:size] = idx
        want = float(value(data, jnp.asarray(pad), size))
        got = (float(control(data, jnp.asarray(pad), size)) if control
               else float(c.out["value"]))
        gaps.append(abs(got - want) / max(abs(want), 1e-12))
        f_values.append(want)
    # With no valid set to value, the gap reads 1 (and invalid_calls fails).
    numbers = {"invalid_calls": invalid,
               "value_gap": max(gaps) if gaps else 1.0}
    return numbers, f_values


def draw_sets(sh, key):
    """Index sets of one check: per state g, ``base`` (G, k/2) columns
    of S_g and, for a filter launch, ``samp`` (G, m, b) further columns
    R_gi; ``skip`` marks the candidates the program zeroes (members of
    the state it scores)."""
    g, n, c = sh["G"], sh["n"], sh["kcap"] // 2
    m, b = sh.get("m", 0), sh.get("b", 0)
    cols = jax.vmap(lambda kk: jax.random.choice(
        kk, n, (c + m * b,), replace=False))(jax.random.split(key, g))
    cols = cols.astype(jnp.int32)
    base = cols[:, :c]
    gi = jnp.arange(g)[:, None]
    if not m:
        return {"base": base,
                "skip": jnp.zeros((g, n), bool).at[gi, base].set(True)}
    samp = cols[:, c:].reshape(g, m, b)
    skip = jnp.zeros((g, m, n), bool)
    skip = skip.at[gi[:, :, None], jnp.arange(m)[None, :, None], samp].set(True)
    skip = skip.at[gi, :, base].set(True)
    return {"base": base, "samp": samp, "skip": skip}


def _ones(x):
    return jnp.ones(x.shape, bool)


def states(obj, base):
    """The objective's states S_g = base[g], built by its own ``add_set``
    at ``highest`` precision: the operands of the launch under check,
    accurate to float32, so its gap is the launch's own."""
    with jax.default_matmul_precision("highest"):
        return jax.jit(lambda o, b: jax.vmap(
            lambda bs: o.add_set(o.init(), bs, _ones(bs)))(b))(obj, base)


def program(role, obj, sets):
    """The objective's own scores of ``sets`` for a launch of ``role``,
    at the objective's settings and the default matmul precision the
    window runs at.  Traced afresh on every call, so it runs the
    objective's code as it stands."""
    st = states(obj, sets["base"])
    if role == "filter":
        return jax.jit(lambda o, st, s: jax.vmap(
            lambda t, ss: o.filter_gains_batch(t, ss, _ones(ss)))(st, s))(
                obj, st, sets["samp"])
    return jax.jit(lambda o, st: jax.vmap(o.gains)(st))(obj, st)


def kernels(cell, obj, data, seed_key, ref, lower=None):
    """{<kernel>_gap: x} for each kernel of the configuration whose
    launch this cell makes.  ``obj`` is the objective the window called;
    with ``lower`` the control's numbers are read in its place."""
    out = {}
    key = jax.random.fold_in(seed_key, CHECK_KEY_TAG)
    for i, (role, name) in enumerate(launch.kernels(cell)):
        sets = draw_sets(launch.shape(cell, role), jax.random.fold_in(key, i))
        chk = cell.module("kernel_checks", name)
        want = np.asarray(chk.reference(cell, data, sets, ref))
        got = np.asarray(chk.reference(cell, data, sets, ref, lower) if lower
                         else program(role, obj, sets))
        keep = ~np.asarray(sets["skip"])
        scale = max(float(np.max(np.abs(want[keep]))), 1e-30)
        out[f"{name}_gap"] = float(np.max(np.abs(got - want)[keep])) / scale
        del sets, want, got, keep
    return out


def judge(numbers: dict, limits: dict):
    """correct, and [(name, value, limit)] for every number compared."""
    rows = []
    ok = True
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for {name!r} in this cell's limits file")
        lim = limits[name]
        rows.append((name, value, lim))
        ok &= bool(value <= lim)
    return ok, rows
