"""One caller in a closed loop: the next select() starts when the last
has returned every output.

Call i uses ``fold_in(call_key, i)``.  The window opens at the first
call and ends at the end of the last call started before ``seconds``
had passed.  A call that raises is recorded as failed and the loop goes
on.  End to end it reports ``select_s``: the window's wall time over the
calls it completed.
"""

from __future__ import annotations

import sys
import time
import traceback
from dataclasses import dataclass


@dataclass
class Call:
    index: int
    start: float            # seconds from the window's start
    end: float
    out: dict | None        # the result's kept leaves, on the device


def run(session, seconds: float, tracer):
    import jax
    from jax.profiler import TraceAnnotation

    with TraceAnnotation("bench.warmup"):
        out = session.call(session.call_key(-1))
        jax.block_until_ready(out)
        del out
    calls = []
    t0 = time.perf_counter()
    session.open_window()
    i = 0
    while time.perf_counter() - t0 < seconds:
        tracer.before_call()
        start = time.perf_counter() - t0
        with TraceAnnotation("bench.select"):
            try:
                res = session.call(session.call_key(i))
                jax.block_until_ready(res)
                out = session.keep(res)
                del res
            except Exception:               # a failed call is counted, not fatal
                traceback.print_exc()
                out = None
        end = time.perf_counter() - t0
        tracer.after_call(end)
        calls.append(Call(i, start, end, out))
        print(f"[window] call {i} {end - start:.3f}s", file=sys.stderr,
              flush=True)
        i += 1
    tracer.stop()
    session.close_window()
    done = [c for c in calls if c.out is not None]
    metrics = {"select_s": calls[-1].end / len(done)} if done else {}
    return t0, calls, metrics
