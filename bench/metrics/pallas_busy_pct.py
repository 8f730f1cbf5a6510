"""Share of the device's busy time spent in the cell's Pallas kernels.

The kernels are the configuration's, for the launches the cell's
traffic makes (``harness.launch``); each is found in the trace by the
names its work count gives (``bench/work/<kernel>.py``: ``HLO_NAMES``).
The rest of the busy time is XLA's own work: sampling, top-k, copies,
solves and the glue between launches.  A trace in which none of the
kernels shows gives no reading, and says so on standard error.
"""

from harness import launch
from harness.trace import base_name, union_length


def read(run):
    t = run.trace
    if t is None or t.window is None or not t.device_ops:
        return None
    names = {n for _, k in launch.kernels(run.cell)
             for n in run.work(k).HLO_NAMES}
    busy = pallas = 0.0
    for p in t.device_ops:
        ops = t.ops(p)
        busy += union_length((s, e) for _, s, e in ops)
        pallas += union_length((s, e) for n, s, e in ops
                               if base_name(n) in names)
    if busy <= 0 or pallas <= 0:
        run.note("pallas_busy_pct: no traced device operation is named "
                 + " or ".join(sorted(names)))
        return None
    return 100.0 * pallas / busy
