"""Share of the roofline of the ``regression_gains`` kernel (see harness.roofline)."""

from harness.roofline import share


def read(run):
    return share(run, "regression_gains")
