"""Public jit'd wrapper for the A-optimality gains kernel.

Padding / block-size / backend routing via ``repro.kernels.common`` +
the ``repro.kernels.tuning`` cache: non-TPU backends run the jnp
reference; interpret mode only when requested explicitly.

``precision="bf16"`` streams BOTH X and W = M⁻¹X in bf16 with f32
reductions; the reference path quantizes them identically.
"""

from __future__ import annotations

from repro.kernels.aopt_gains.kernel import aopt_gains_pallas
from repro.kernels.aopt_gains.ref import aopt_gains_ref
from repro.kernels.common import (
    pad2d,
    quantize,
    resolve_path,
    resolve_precision,
    round_up,
    stream_dtype,
    stream_resident_bytes,
    sublane_for,
)
from repro.kernels.tuning import bucket_n, tuned_block_n


def aopt_gains(X, W, isig2, *, interpret: bool | None = None,
               precision: str | None = None, block_n: int | None = None):
    """Batched Sherman–Morrison gains; Pallas on TPU, reference elsewhere."""
    use_ref, interpret = resolve_path(interpret)
    prec = resolve_precision(precision)
    sdt = stream_dtype(prec)
    sb = stream_resident_bytes(prec)
    d, n = X.shape
    dp = round_up(d, sublane_for(sdt))
    # X and W blocks both stream at the policy precision; out row is f32.
    vmem = lambda bn: 2 * sb * dp * bn + 4 * bn
    bn = block_n or tuned_block_n(
        "aopt_gains", prec, {"dp": dp, "nb": bucket_n(n)}, vmem,
    )
    np_ = round_up(n, bn)
    if use_ref:
        return aopt_gains_ref(quantize(X, prec), quantize(W, prec), isig2)
    Xp = pad2d(X, dp, np_, dtype=sdt)
    Wp = pad2d(W, dp, np_, dtype=sdt)
    out = aopt_gains_pallas(Xp, Wp, isig2=float(isig2), block_n=bn,
                            interpret=interpret)
    return out[:n]
