"""Share of the traced window in which no operation ran on the device.

1 − (union of the device's operation intervals) / (window), the window
running from the first traced call's start to the last one's end,
averaged over the chips used.
"""


def read(run):
    t = run.trace
    if t is None or t.window is None or not t.device_ops:
        return None
    w = t.window[1] - t.window[0]
    idle = [1.0 - t.busy_ns(p) / w for p in t.device_ops]
    return 100.0 * sum(idle) / len(idle)
