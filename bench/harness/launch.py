"""The kernel launches a cell makes, declared by its files.

A configuration names the kernel that plays each role for its
objective (``kernels`` in ``bench/configs/<config>.json``):

    "filter"   scores every candidate at each perturbed state S_g ∪ R_gi
               of a (guess, sample) lattice: ``filter_gains_batch``;
    "sweep"    scores every candidate at states S_g: ``gains``.

A traffic mix declares the launches its algorithm makes, by role, and
their shape (``launches`` in ``bench/traffic/<traffic>.json``):

    "filter": {"guesses": G, "samples": m, "r": r}     b = ⌈k / r⌉ columns
              or {"guesses": G, "samples": m, "block": b}
    "sweep":  {"states": s}    the fewest states one traced launch scores

The work counts (``bench/work``), the roofline shares and the kernel
checks read the shapes from here and from nowhere else, so none of them
repeats a default of the program, and a new mix states its own launches
in its own file.
"""

from __future__ import annotations

import math

ROLES = ("filter", "sweep")


def kernels(cell):
    """[(role, kernel)] of the configuration whose role the traffic
    launches, in the configuration's order."""
    launches = cell.traffic.get("launches", {})
    return [(role, name) for role, name in cell.config["kernels"].items()
            if role in launches]


def role_of(cell, kernel):
    for role, name in kernels(cell):
        if name == kernel:
            return role
    return None


def shape(cell, role):
    """Logical shape of the cell's ``role`` launches, or None where the
    traffic makes none."""
    decl = cell.traffic.get("launches", {}).get(role)
    if decl is None:
        return None
    if role not in ROLES:
        raise ValueError(f"unknown launch role {role!r}; have {ROLES}")
    s = cell.sizes
    k = int(s["k"])
    out = {"d": int(s["d"]), "n": int(s["n"]), "kcap": k}
    if role == "filter":
        b = int(decl["block"]) if "block" in decl else math.ceil(
            k / int(decl["r"]))
        out.update(G=int(decl["guesses"]), m=int(decl["samples"]), b=b)
    else:
        out.update(G=int(decl.get("states", 1)))
    return out
