"""D1 regression data (Qian & Singer 2019, §5 / App. I.2), made on the device.

X holds n candidate features of d samples each: one-factor correlated
normals (pairwise correlation ρ through a shared per-sample factor),
each column centred and scaled to unit norm.  y = X_raw[:, S*] β + noise
with β ~ U(lo, hi) on a planted support S* of ``support`` features.

X is written column chunk by column chunk inside one jitted call, so
the generator's own peak stays near X itself.  Column statistics are
per column, so chunking changes nothing; y is built from the raw
support columns recovered as ``X[:, j] · norm_j + mean_j``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=(
    "d", "n", "support", "rho", "noise", "beta_lo", "beta_hi", "chunk"))
def _generate(key, *, d, n, support, rho, noise, beta_lo, beta_hi, chunk):
    kx, ks, kb, kn = jax.random.split(key, 4)
    kc, ke = jax.random.split(kx)
    common = jax.random.normal(kc, (d, 1))

    def body(j, carry):
        X, mean, norm = carry
        e = jax.random.normal(jax.random.fold_in(ke, j), (d, chunk))
        raw = math.sqrt(rho) * common + math.sqrt(1.0 - rho) * e
        mu = jnp.mean(raw, axis=0)
        cen = raw - mu[None, :]
        nrm = jnp.sqrt(jnp.sum(cen * cen, axis=0))
        X = jax.lax.dynamic_update_slice(X, cen / nrm[None, :], (0, j * chunk))
        mean = jax.lax.dynamic_update_slice(mean, mu, (j * chunk,))
        norm = jax.lax.dynamic_update_slice(norm, nrm, (j * chunk,))
        return X, mean, norm

    X, mean, norm = jax.lax.fori_loop(
        0, n // chunk, body,
        (jnp.zeros((d, n), jnp.float32), jnp.zeros((n,), jnp.float32),
         jnp.zeros((n,), jnp.float32)))
    sup = jax.random.choice(ks, n, (support,), replace=False)
    beta = jax.random.uniform(kb, (support,), minval=beta_lo, maxval=beta_hi)
    raw_sup = X[:, sup] * norm[sup][None, :] + mean[sup][None, :]
    with jax.default_matmul_precision("highest"):
        y = raw_sup @ beta + noise * jax.random.normal(kn, (d,))
    return X, y


def generate(key, sizes: dict, params: dict) -> dict:
    d, n = int(sizes["d"]), int(sizes["n"])
    chunk = min(n, int(params.get("chunk", 1 << 16)))
    if n % chunk:
        raise ValueError(f"n={n} is not a multiple of chunk={chunk}")
    X, y = _generate(
        key, d=d, n=n, support=int(sizes["support"]), rho=float(params["rho"]),
        noise=float(params["noise"]), beta_lo=float(params["beta"][0]),
        beta_hi=float(params["beta"][1]), chunk=chunk)
    return {"X": X, "y": y}
