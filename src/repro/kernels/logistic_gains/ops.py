"""Public jit'd wrapper for the logistic-gains kernel.

Padding / block-size / backend routing via ``repro.kernels.common`` +
the ``repro.kernels.tuning`` cache: non-TPU backends run the jnp
reference; interpret mode only when requested explicitly.

``precision="bf16"`` streams X in bf16; the Newton recurrence (and the
labels/logits columns) stays f32, and the reference path quantizes X
identically.
"""

from __future__ import annotations

from repro.kernels.common import (
    pad1d,
    pad2d,
    quantize,
    resolve_path,
    resolve_precision,
    round_up,
    stream_dtype,
    stream_resident_bytes,
    sublane_for,
)
from repro.kernels.logistic_gains.kernel import logistic_gains_pallas
from repro.kernels.logistic_gains.ref import logistic_gains_ref
from repro.kernels.tuning import bucket_n, tuned_block_n


def logistic_gains(X, y, eta, *, steps: int = 3,
                   interpret: bool | None = None,
                   precision: str | None = None,
                   block_n: int | None = None):
    use_ref, interpret = resolve_path(interpret)
    prec = resolve_precision(precision)
    sdt = stream_dtype(prec)
    sb = stream_resident_bytes(prec)
    d, n = X.shape
    dp = round_up(d, sublane_for(sdt))
    # X block at stream precision (+ f32 upcast); y/η columns and the
    # per-candidate rows stay f32.
    vmem = lambda bn: sb * dp * bn + 4 * (2 * dp + 4 * bn)
    bn = block_n or tuned_block_n(
        "logistic_gains", prec,
        {"dp": dp, "steps": steps, "nb": bucket_n(n)}, vmem,
    )
    np_ = round_up(n, bn)
    if use_ref:
        return logistic_gains_ref(quantize(X, prec), y, eta, steps=steps)
    Xp = pad2d(X, dp, np_, dtype=sdt)
    yp = pad1d(y, dp)
    ep = pad1d(eta, dp)
    out = logistic_gains_pallas(Xp, yp, ep, steps=steps, block_n=bn,
                                interpret=interpret)
    return out[:n]
