"""Share of the traced window in which the device is idle while the
host runs the program's own code.

The device's idle gaps (``harness.trace.TraceView.idle_gaps``)
intersected with the host spans the program opens (``repro.select``,
``repro.dash.guesses``, ``repro.dash.lattice``, ``repro.dash.best``:
every span named ``repro.*``), over the window, averaged over the chips
used.  The rest of ``device_idle_pct`` falls outside the program's
spans: the caller's own host work between calls.  A trace without the
program's spans gives no reading, and says so on standard error.
"""

from harness.scopes import PREFIX
from harness.trace import merge


def read(run):
    t = run.trace
    if t is None or t.window is None or not t.device_ops:
        return None
    w0, w1 = t.window
    spans = merge((max(s, w0), min(e, w1)) for n, s, e in t.host_spans
                  if n.startswith(PREFIX) and min(e, w1) > max(s, w0))
    if not spans:
        run.note(f"entry_idle_pct: no host span named {PREFIX}* in the "
                 "traced window")
        return None
    shares = []
    for p in t.device_ops:
        idle = sum(max(0, min(ge, se) - max(gs, ss))
                   for gs, ge in t.idle_gaps(p) for ss, se in spans)
        shares.append(idle / (w1 - w0))
    return 100.0 * sum(shares) / len(shares)
