"""Required work of one launch of the A-optimality gains kernel.

One launch scores every candidate a at G states from each state's
shared solve W_g = M_g⁻¹X (shape: ``harness.launch``, role "sweep", G
the fewest states a launch scores):
gain(a) = σ⁻²‖w_a‖² / (1 + σ⁻² x_aᵀw_a) (``kernels/aopt_gains/ref.py``):

* FLOPs: ‖w_a‖² and x_aᵀw_a (2d each) per candidate and state;
* bytes: X read once (4dn), each W_g once (4·G·d·n), the gains written
  once (4·G·n).  No padding is credited.
"""

HLO_NAMES = ("aopt_gains_pallas",)


def per_launch(sh):
    d, n, g = sh["d"], sh["n"], sh["G"]
    return float(4 * g * d * n), float(4 * (d * n + g * d * n + g * n))
