"""A run whose timed path is broken underneath comes out not correct.

Each fault is planted in the program (never in the harness), the run is
driven as usual with the CPU standing in for the chip, and ``correct``
must read false: a state update that returns its state unchanged, an
answer altered where it is produced (the reported f(S), or the selected
set with half its members swapped for unselected candidates), and half
of the candidates left out of the gain sweeps.
"""

import pytest

from conftest import run_cell


def _unchanged_state(monkeypatch):
    import repro.core as core

    for cls in (core.RegressionObjective, core.AOptimalityObjective):
        monkeypatch.setattr(cls, "add_set",
                            lambda self, state, idx, mask: state)


def _wrap_select(monkeypatch, alter):
    import repro.core as core

    orig = core.select
    monkeypatch.setattr(core, "select",
                        lambda *a, **kw: alter(orig(*a, **kw)))


def _value_altered(monkeypatch):
    _wrap_select(monkeypatch, lambda r: r._replace(value=r.value * 1.05))


def _index_altered(monkeypatch):
    import jax.numpy as jnp

    def alter(r):
        # Swap half of the selected candidates for unselected ones; the
        # reported f(S) and |S| stay as they were.
        m = r.sel_mask
        half = jnp.cumsum(m) <= jnp.sum(m) // 2
        out = m & ~half
        free = jnp.cumsum(~m) <= jnp.sum(m & half)
        return r._replace(sel_mask=out | (~m & free))

    _wrap_select(monkeypatch, alter)


def _half_left_out(monkeypatch):
    import repro.kernels.aopt_gains.ops as aops
    import repro.kernels.filter_gains.ops as fops
    import repro.kernels.marginal_gains.ops as mops

    def halve(fn):
        def wrapped(*a, **kw):
            g = fn(*a, **kw)
            n = g.shape[-1]
            return g.at[..., n // 2:].set(0.0)
        return wrapped

    monkeypatch.setattr(mops, "regression_gains", halve(mops.regression_gains))
    monkeypatch.setattr(aops, "aopt_gains", halve(aops.aopt_gains))
    monkeypatch.setattr(fops, "filter_gains", halve(fops.filter_gains))
    monkeypatch.setattr(fops, "aopt_filter_gains",
                        halve(fops.aopt_filter_gains))


FAULTS = {"unchanged_state": _unchanged_state,
          "value_altered": _value_altered,
          "index_altered": _index_altered,
          "half_left_out": _half_left_out}


@pytest.mark.parametrize("workload", ["d1-regression.dash", "d1-design.dash"])
@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_not_correct(tiny_root, capsys, monkeypatch, workload, fault):
    FAULTS[fault](monkeypatch)
    rc, line = run_cell(tiny_root, workload, capsys, seconds=0.5)
    assert rc == 0
    assert line["correct"] is False, line["checks"]
