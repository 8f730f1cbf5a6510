"""Required work of one launch of the regression filter kernel.

One launch scores every candidate a at G·m perturbed states: guess g's
shared basis Q_g plus sample i's delta basis D_gi (b columns) and
residual r_gi (shape: ``harness.launch``, role "filter").  Counted from
the logical shapes and the mathematics (``kernels/filter_gains/ref.py``),
never from the implementation:

* FLOPs: per (g, i), x_aᵀr_gi (2d) and ‖D_giᵀx_a‖² (2db + 2b) per
  candidate.  ‖x_a‖² − ‖Q_gᵀx_a‖² is a per-candidate vector of the
  guess's state that a launch need not re-derive, so it is not credited;
* bytes: X read once (4dn), that vector once per guess (4·G·n), the
  deltas and residuals once (4·G·m·d·(b + 1)), the (G·m, n) gains
  written once.  No padding is credited.
"""

HLO_NAMES = ("filter_gains_pallas",)


def per_launch(sh):
    d, n, g, b = sh["d"], sh["n"], sh["G"], sh["b"]
    gm = g * sh["m"]
    flops = gm * n * (2 * d + 2 * d * b + 2 * b)
    nbytes = 4 * (d * n + g * n + gm * d * (b + 1) + gm * n)
    return float(flops), float(nbytes)
