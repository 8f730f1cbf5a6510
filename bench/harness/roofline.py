"""A kernel's share of its roofline, from the trace and its work count.

The least time of a launch is the larger of its required FLOPs over the
device's peak rate and its required bytes over HBM bandwidth
(``bench/work/<kernel>.py`` at the shape ``harness.launch`` declares,
``bench/peaks.json``).  The share is the least time of all the traced
launches over their summed device time.  An event nested in another
of the kernel's events is part of that launch, not one of its own.  A
cell whose traffic makes no launch of the kernel gives no reading; one
that makes launches the trace does not show is reported on standard
error, and gives none.
"""

from harness import launch
from harness.trace import outermost


def share(run, kernel: str):
    if run.trace is None:
        return None
    role = launch.role_of(run.cell, kernel)
    if role is None:
        return None
    work = run.work(kernel)
    events = outermost((s, e) for _, s, e in
                   run.trace.kernel_events(work.HLO_NAMES))
    if not events:
        run.note(f"{kernel}: the cell launches it, but no traced device "
                 f"operation is named {' or '.join(work.HLO_NAMES)}")
        return None
    flops, nbytes = work.per_launch(launch.shape(run.cell, role))
    t_flops = flops / run.peaks["flops_per_s"]
    t_bytes = nbytes / run.peaks["hbm_bytes_per_s"]
    spent = sum(e - s for s, e in events) / 1e9
    run.note(f"{kernel}: {len(events)} launches, {spent:.6f} s on device, "
             f"least {max(t_flops, t_bytes):.6f} s per launch, bound by "
             f"{'FLOPs' if t_flops >= t_bytes else 'HBM bytes'}")
    return 100.0 * len(events) * max(t_flops, t_bytes) / spent
