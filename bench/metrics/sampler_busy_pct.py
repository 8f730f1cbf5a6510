"""Share of the device's busy time spent in the program's sampler.

The device operations whose op-name path holds the ``repro.sample``
scope (``core/estimators.py::sample_set_from_mask``: the Gumbel noise,
the mask and the top-k, wherever the selection loop draws a set), as a
union of intervals over the union of all operations' intervals, summed
over the chips used (``harness.scopes``).  A trace without the
program's scopes gives no reading, and says so on standard error.
"""

from harness.scopes import busy_pct


def read(run):
    return busy_pct(run, "repro.sample", "sampler_busy_pct")
