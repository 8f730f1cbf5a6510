"""Required work of one launch of the regression marginal-gains kernel.

One launch scores every candidate a at G states (basis Q_g, residual
r_g; shape: ``harness.launch``, role "sweep", G the fewest states a
launch scores): gain(a) = (x_aᵀr_g)² / (‖x_a‖² − ‖Q_gᵀx_a‖²)
(``kernels/marginal_gains/ref.py``):

* FLOPs: x_aᵀr_g (2d) per candidate and state.  The denominator is a
  per-candidate vector of the state that a launch need not re-derive,
  so it is not credited;
* bytes: X read once (4dn), per state the denominator (4n) and r_g (4d)
  once and the gains written once (4n).  No padding is credited.
"""

HLO_NAMES = ("regression_gains_pallas",)


def per_launch(sh):
    d, n, g = sh["d"], sh["n"], sh["G"]
    return float(2 * g * d * n), float(4 * (d * n + g * (2 * n + d)))
