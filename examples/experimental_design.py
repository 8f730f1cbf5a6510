"""Bayesian A-optimal experimental design (paper §3.1 Cor. 9 + App. D),
optimized by the DISTRIBUTED DASH runtime — the smoke-runnable demo of
``dash_distributed``: stimuli columns sharded over the ``model`` mesh
axis, Monte-Carlo replicas over ``data``, the same shared selection loop
as single-device ``dash``.

    PYTHONPATH=src python examples/experimental_design.py

runs on however many devices the host exposes (a 1-device mesh is fine);
to exercise a pod-in-miniature:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python examples/experimental_design.py

A second section keeps the diversity-regularized single-device variant
(ClusterDiversity + DiversifiedObjective) for comparison.
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    AOptimalityObjective,
    ClusterDiversity,
    DiversifiedObjective,
    dash_auto,
    gamma_aopt,
    alpha_from_gamma,
    greedy,
)
from repro.core.dash import DashConfig
from repro.core.distributed import dash_distributed, pad_ground_set
from repro.data.synthetic import make_d1_design
from repro.launch.mesh import make_host_mesh
from repro.utils.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    X = make_d1_design(seed=0, n_samples=512, n_features=128)
    k = 32

    # γ from the paper's closed form (Cor. 9) → α = γ²
    gamma = float(gamma_aopt(jnp.asarray(X), 1.0, 1.0))
    alpha = max(float(alpha_from_gamma(gamma)), 0.3)   # floor for practice
    print(f"γ (Cor. 9 bound) = {gamma:.4f}; practical α = {alpha:.3f}")

    # ---- distributed DASH: stimuli sharded over the model axis ----------
    mesh = make_host_mesh()
    model_size = mesh.shape["model"]
    Xp, n_real = pad_ground_set(jnp.asarray(X), model_size)
    base = AOptimalityObjective(Xp, kmax=k, beta2=1.0, sigma2=1.0)

    g = greedy(base, k)
    cfg = DashConfig(k=k, eps=0.25, alpha=alpha, n_samples=8)
    res = dash_distributed(base, cfg, jax.random.PRNGKey(0),
                           float(g.value) * 1.05, mesh)
    mesh_shape = "x".join(str(s) for s in mesh.devices.shape)
    print(f"greedy:           f_A = {float(g.value):.4f} ({k} rounds)")
    print(f"DASH distributed: f_A = {float(res.value):.4f} "
          f"({int(res.rounds)} adaptive rounds, mesh {mesh_shape}, "
          f"|S| = {int(res.sel_count)})")
    assert not bool(jnp.any(res.sel_mask[n_real:])), "padding was selected"

    # ---- diversity-regularized single-device variant --------------------
    # stimuli clustered by sign pattern of their top-2 PCs
    U, _, _ = np.linalg.svd(np.asarray(X), full_matrices=False)
    proj = np.asarray(X).T @ U[:, :2]
    clusters = (proj[:, 0] > 0).astype(np.int32) * 2 + (proj[:, 1] > 0)
    div = ClusterDiversity(jnp.asarray(clusters), 4, weight=0.2)
    obj = DiversifiedObjective(
        AOptimalityObjective(jnp.asarray(X), kmax=k, beta2=1.0, sigma2=1.0),
        div,
    )
    res_div = dash_auto(obj, k, jax.random.PRNGKey(0), eps=0.25, alpha=alpha,
                        n_samples=8, n_guesses=6)
    print(f"DASH + diversity: f_A-div = {float(res_div.value):.4f} "
          f"({int(res_div.rounds)} adaptive rounds)")

    counts = np.bincount(clusters[np.asarray(res_div.sel_mask)], minlength=4)
    print(f"cluster coverage of diversified selection: {counts.tolist()}")


if __name__ == "__main__":
    main()
