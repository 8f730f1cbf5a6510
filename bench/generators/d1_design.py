"""D1 experimental-design data (Qian & Singer 2019, §5 / App. I.2), on device.

Each of the n candidate stimuli is a column of d correlated normal
coordinates (correlation ρ through a factor shared by the column's
coordinates), scaled to unit ℓ2 norm.  Written chunk by chunk inside
one jitted call, so the generator's peak stays near X itself.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, static_argnames=("d", "n", "rho", "chunk"))
def _generate(key, *, d, n, rho, chunk):
    kc, ke = jax.random.split(key)

    def body(j, X):
        common = jax.random.normal(jax.random.fold_in(kc, j), (1, chunk))
        e = jax.random.normal(jax.random.fold_in(ke, j), (d, chunk))
        raw = math.sqrt(rho) * common + math.sqrt(1.0 - rho) * e
        nrm = jnp.sqrt(jnp.sum(raw * raw, axis=0, keepdims=True))
        return jax.lax.dynamic_update_slice(X, raw / nrm, (0, j * chunk))

    return jax.lax.fori_loop(0, n // chunk, body,
                             jnp.zeros((d, n), jnp.float32))


def generate(key, sizes: dict, params: dict) -> dict:
    d, n = int(sizes["d"]), int(sizes["n"])
    chunk = min(n, int(params.get("chunk", 1 << 16)))
    if n % chunk:
        raise ValueError(f"n={n} is not a multiple of chunk={chunk}")
    return {"X": _generate(key, d=d, n=n, rho=float(params["rho"]),
                           chunk=chunk)}
