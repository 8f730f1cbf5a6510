"""Public jit'd wrapper for the marginal-gains kernel.

Pads shapes to TPU-friendly multiples, picks a block size (tuned winner
if the persistent autotuning cache has one for this shape bucket, VMEM
heuristic otherwise — shared via ``repro.kernels.common`` /
``repro.kernels.tuning``), and routes non-TPU backends to the jnp
reference.  Pallas interpret mode is reachable only by passing
``interpret=True`` explicitly — it validates the kernel on CPU but is
orders of magnitude slower than the reference, so it is never an
implicit fallback.

``precision="bf16"`` streams X in bf16 with f32 accumulation; the
reference path quantizes X identically so both routes compute the same
function per precision.
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
from repro.kernels.marginal_gains.kernel import regression_gains_pallas
from repro.kernels.marginal_gains.ref import SPAN_TOL, regression_gains_ref
from repro.kernels.tuning import bucket_n, tuned_block_n


def regression_gains(X, Q, resid, col_sq, *, interpret: bool | None = None,
                     precision: str | None = None,
                     block_n: int | None = None):
    """Batched regression gains; Pallas on TPU, jnp reference elsewhere."""
    use_ref, interpret = resolve_path(interpret)
    prec = resolve_precision(precision)
    sdt = stream_dtype(prec)
    sb = stream_resident_bytes(prec)
    d, n = X.shape
    k = Q.shape[1]
    dp = round_up(d, sublane_for(sdt))
    kp = round_up(max(k, 1), sublane_for(sdt))
    # Bytes resident per grid step: X block at stream precision (+ its
    # f32 upcast), then f32 Q, resid, col_sq + out.
    vmem = lambda bn: sb * dp * bn + 4 * (dp * (kp + 1) + 2 * bn)
    bn = block_n or tuned_block_n(
        "regression_gains", prec,
        {"dp": dp, "kp": kp, "nb": bucket_n(n)}, vmem,
    )
    np_ = round_up(n, bn)
    if use_ref:
        return regression_gains_ref(quantize(X, prec), Q, resid, col_sq)

    Xp = pad2d(X, dp, np_, dtype=sdt)
    Qp = pad2d(Q, dp, kp)
    rp = pad1d(resid, dp)
    # Padded columns are all-zero: give them col_sq = 1 so the span guard
    # clamps their gain to 0 instead of dividing 0/0.
    cp = pad1d(col_sq, np_, fill=1.0)
    out = regression_gains_pallas(
        Xp, Qp, rp, cp, block_n=bn, span_tol=SPAN_TOL, interpret=interpret
    )
    return out[:n]
