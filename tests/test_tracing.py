"""The program's own trace marks: named scopes in the compiled selection
loop and profiler spans round ``select()``.

The scopes (``repro.round``, ``repro.estimate``, ``repro.filter``,
``repro.sample``, ``repro.add_set``) reach the TPU profile through each
XLA operation's op-name metadata, which the compiled HLO shows; the
spans are TraceMe events on the profiler's host plane.  Neither may
change what is computed.
"""

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AOptimalityObjective,
    RegressionObjective,
    dash_auto,
    select,
)

SCOPES = ("repro.round", "repro.estimate", "repro.filter", "repro.sample",
          "repro.add_set")
DASH = dict(n_guesses=2, n_samples=4, r=4)


def _objective(kind, use_filter_engine=True):
    """A tiny objective on which some guess's filter loop iterates (four
    stimuli dimensions saturate within k = 8 picks)."""
    rng = np.random.default_rng(3)
    d, n = (4, 256) if kind == "aopt" else (24, 256)
    X = rng.normal(size=(d, n))
    X = jnp.asarray(X / np.linalg.norm(X, axis=0, keepdims=True), jnp.float32)
    if kind == "aopt":
        return AOptimalityObjective(X, kmax=8, sigma2=0.1,
                                    use_filter_engine=use_filter_engine)
    y = X[:, :4] @ jnp.asarray([1.0, -2.0, 0.5, 1.5]) + 0.05 * jnp.asarray(
        rng.normal(size=d), jnp.float32)
    return RegressionObjective(X, y, kmax=8,
                               use_filter_engine=use_filter_engine)


def _scopes_in_hlo(obj, k=8, **opts):
    run = jax.jit(lambda o, key: select("dash", o, k, key, **opts).sel_mask)
    text = run.lower(obj, jax.random.PRNGKey(0)).compile().as_text()
    found = set()
    for path in re.findall(r'op_name="([^"]*)"', text):
        for c in path.split("/"):
            found.update(re.findall(r"repro\.[a-z_]+", c))
    return found


@pytest.mark.parametrize("kind", ["aopt", "regression"])
def test_scopes_reach_the_compiled_hlo(kind):
    assert set(SCOPES) <= _scopes_in_hlo(_objective(kind), **DASH)


def test_per_sample_state_update_is_scoped():
    """Without the filter engine, the filter's perturbed states are the
    objective's own ``add_set``, under ``repro.add_set`` too."""
    obj = _objective("aopt", use_filter_engine=False)
    run = jax.jit(lambda o, key: select("dash", o, 8, key, **DASH).sel_mask)
    text = run.lower(obj, jax.random.PRNGKey(0)).compile().as_text()
    assert re.search(r'op_name="[^"]*repro\.filter/[^"]*repro\.add_set',
                     text)


@contextlib.contextmanager
def _no_scopes(monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        yield


@pytest.mark.parametrize("kind", ["aopt", "regression"])
def test_scopes_change_no_selection(kind, monkeypatch):
    """Same key, same bits, with the scopes and without them, for every
    guess of the lattice; and some guess's filter loop runs, so its
    scope is exercised too."""
    key = jax.random.PRNGKey(11)
    best, lattice = dash_auto(_objective(kind), 8, key, return_lattice=True,
                              **DASH)
    with _no_scopes(monkeypatch):
        bare_obj = _objective(kind)          # fresh runners, traced bare
        assert not _scopes_in_hlo(bare_obj, **DASH)
        bare_best, bare = dash_auto(bare_obj, 8, key, return_lattice=True,
                                    **DASH)
    for a, b in ((best, bare_best), (lattice, bare)):
        np.testing.assert_array_equal(np.asarray(a.sel_mask),
                                      np.asarray(b.sel_mask))
        assert np.asarray(a.value).tobytes() == np.asarray(b.value).tobytes()
        np.testing.assert_array_equal(np.asarray(a.rounds),
                                      np.asarray(b.rounds))
    assert int(jnp.sum(lattice.trace.filter_iters)) > 0


def _host_spans(directory):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                           {k: v for k, v in ev.stats}) for ev in line.events
                          if ev.name.startswith("repro.")]
    return spans


def test_host_spans_nest_in_select(tmp_path):
    obj = _objective("aopt")
    key = jax.random.PRNGKey(0)
    jax.block_until_ready(select("dash", obj, 8, key, **DASH))   # compile
    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(select("dash", obj, 8, key, **DASH))
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    outer = [s for s in spans if s[0] == "repro.select"]
    assert len(outer) == 1
    _, s0, e0, args = outer[0]
    assert args.get("algo") == "dash" and int(args.get("k")) == 8
    inner = {n: (s, e) for n, s, e, _ in spans if n != "repro.select"}
    steps = ("repro.dash.guesses", "repro.dash.lattice", "repro.dash.best")
    assert set(steps) <= set(inner)
    for n in steps:
        assert s0 <= inner[n][0] <= inner[n][1] <= e0, n
    starts = [inner[n][0] for n in steps]
    assert starts == sorted(starts)
