"""Quickstart: DASH vs greedy feature selection on the paper's D1 setup.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.core import (
    RegressionObjective,
    dash_auto,
    greedy,
    random_select,
    top_k_select,
)
from repro.data.synthetic import make_d1_regression
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    X, y, support = make_d1_regression(seed=0, n_samples=600,
                                       n_features=200, support=40)
    k = 40
    obj = RegressionObjective(jnp.asarray(X), jnp.asarray(y), kmax=k)

    g = greedy(obj, k)
    print(f"greedy (SDS_MA):  value={float(g.value):.4f}  rounds={k}")

    res = dash_auto(obj, k, jax.random.PRNGKey(0), eps=0.25, alpha=0.6,
                    n_samples=8, n_guesses=6)
    print(f"DASH:             value={float(res.value):.4f}  "
          f"rounds={int(res.rounds)}  selected={int(res.sel_count)}")

    t = top_k_select(obj, k)
    r = random_select(obj, k, jax.random.PRNGKey(1))
    print(f"TOP-K:            value={float(t.value):.4f}")
    print(f"RANDOM:           value={float(r.value):.4f}")

    # recovery of the planted support
    sel = set(int(i) for i in jnp.nonzero(res.sel_mask)[0])
    hit = len(sel & set(int(s) for s in support))
    print(f"planted-support recovery: {hit}/{k}")


if __name__ == "__main__":
    main()
