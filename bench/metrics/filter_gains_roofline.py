"""Share of the roofline of the ``filter_gains`` kernel (see harness.roofline)."""

from harness.roofline import share


def read(run):
    return share(run, "filter_gains")
