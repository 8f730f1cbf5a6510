"""Share of the roofline of the ``aopt_gains`` kernel (see harness.roofline)."""

from harness.roofline import share


def read(run):
    return share(run, "aopt_gains")
