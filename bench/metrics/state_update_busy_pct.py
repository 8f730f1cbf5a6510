"""Share of the device's busy time spent updating the objective's state.

The device operations whose op-name path holds the ``repro.add_set``
scope (the objective's ``add_set`` where the selection loop's hooks call
it: for A-optimal design the Cholesky factor and the shared solve
W = M⁻¹X), as a union of intervals over the union of all operations'
intervals, summed over the chips used (``harness.scopes``).  A trace
without the program's scopes gives no reading, and says so on standard
error.
"""

from harness.scopes import busy_pct


def read(run):
    return busy_pct(run, "repro.add_set", "state_update_busy_pct")
