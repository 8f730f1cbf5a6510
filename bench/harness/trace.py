"""Profiler capture and its reduction to device intervals.

A traced run records a short stretch of whole calls with the JAX
profiler.  The ``.xplane.pb`` it writes is read here, with nothing but
JAX, into a :class:`TraceView`: the device's operations (name, start,
end) from each TPU plane's ``XLA Ops`` line, and the host spans of the
benchmark's own ``TraceAnnotation`` s and of JAX's dispatch, all on the
profiler's one clock (nanoseconds).  Per-layer metrics read the view.

No ``pallas_call`` of the program passes a ``name=``, so a kernel's
launch is known by the jitted wrapper it is called through
(``*_pallas``).  XLA names the launch after it (``filter_gains_pallas.3``,
or ``vmap_jit_filter_gains_pallas__.1`` under ``vmap``), and
:func:`base_name` maps both to ``filter_gains_pallas``.  A TPU profile
names an operation by its whole HLO text (``%filter_gains_pallas.3 =
f32[64,1,1048576]{...} custom-call(...), ...``): the instruction's name
is read from before the `` = ``.  An operation with another name whose
op-name path in the profile names such a wrapper
(``jit(filter_gains_pallas)``) is read under the innermost one.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
CALL_SPAN = "bench.select"
_SUFFIX = re.compile(r"\.\d+$")
_TRANSFORMS = re.compile(r"^(?:(?:vmap|jit|pjit)_)+")
_WRAPPER = re.compile(r"jit\(([A-Za-z0-9_]+_pallas)\)")
_HLO_TEXT = re.compile(r"^%?([A-Za-z0-9_.\-]+) = ")
_OPCODE = re.compile(r"^%?[A-Za-z0-9_.\-]+ = .*? ([a-z][a-z0-9\-]*)\(")
CONTAINERS = ("while", "conditional", "call")   # ops that hold other ops


def hlo_name(name: str) -> str:
    """The instruction's name where ``name`` is its whole HLO text."""
    m = _HLO_TEXT.match(name)
    return m.group(1) if m else name


def opcode(name: str) -> str:
    """The operation's HLO opcode (``custom-call``, ``while``, ...), or
    where the name is no HLO text, the name without its uniquifier."""
    m = _OPCODE.match(name)
    return m.group(1) if m else _SUFFIX.sub("", name)


def op_key(name: str) -> str:
    """What the breakdown lists an operation under: a kernel by its
    wrapper, any other operation by its instruction name."""
    base = base_name(name)
    return base if base.endswith("_pallas") else hlo_name(name)


def base_name(name: str) -> str:
    """HLO instruction name without its ``.N`` uniquifier, and for a
    kernel wrapper without the transformations XLA prefixes to it."""
    name = _SUFFIX.sub("", hlo_name(name))
    bare = _TRANSFORMS.sub("", name).rstrip("_")
    return bare if bare.endswith("_pallas") else name


@dataclass
class TraceView:
    """Device operations and host spans of one traced stretch."""

    device_ops: dict = field(default_factory=dict)   # plane -> [(name, s, e)]
    host_spans: list = field(default_factory=list)   # [(name, s, e)]

    @property
    def window(self):
        """(start, end) of the traced calls: first call span's start to
        the last one's end."""
        calls = [(s, e) for n, s, e in self.host_spans if n == CALL_SPAN]
        if not calls:
            return None
        return min(s for s, _ in calls), max(e for _, e in calls)

    def ops(self, plane=None):
        """Device operations clipped to the window, all planes unless
        ``plane`` is given."""
        w = self.window
        planes = [plane] if plane else sorted(self.device_ops)
        out = []
        for p in planes:
            for n, s, e in self.device_ops.get(p, ()):
                if w is not None:
                    s, e = max(s, w[0]), min(e, w[1])
                if e > s:
                    out.append((n, s, e))
        return out

    def busy_ns(self, plane) -> float:
        """Length of the union of the plane's operation intervals."""
        return union_length((s, e) for _, s, e in self.ops(plane))

    def idle_gaps(self, plane):
        """[(start, end)] of the window not covered by any operation."""
        w = self.window
        if w is None:
            return []
        gaps, cur = [], w[0]
        for s, e in merge((s, e) for _, s, e in self.ops(plane)):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if w[1] > cur:
            gaps.append((cur, w[1]))
        return gaps

    def host_at(self, t):
        """Innermost host span (shortest) containing time ``t``, other
        than the benchmark's per-call span."""
        best = None
        for n, s, e in self.host_spans:
            if s <= t <= e and n != CALL_SPAN and (
                    best is None or e - s < best[2] - best[1]):
                best = (n, s, e)
        return best[0] if best else CALL_SPAN

    def kernel_events(self, hlo_names):
        names = set(hlo_names)
        return [(n, s, e) for n, s, e in self.ops() if base_name(n) in names]


def outermost(intervals):
    """The intervals that lie in no other one (a launch, without the
    events nested in it)."""
    out = []
    for s, e in sorted(intervals, key=lambda iv: (iv[0], -iv[1])):
        if not out or e > out[-1][1]:
            out.append((s, e))
    return out


def merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def union_length(intervals) -> float:
    return float(sum(e - s for s, e in merge(intervals)))


def op_name(ev) -> str:
    """The event's name, or, where that names no kernel wrapper, the
    innermost ``jit(*_pallas)`` of the op-name path in its stats."""
    if base_name(ev.name).endswith("_pallas"):
        return ev.name
    found = []
    for k, v in ev.stats:
        if isinstance(v, str) and "module" not in k:
            found = _WRAPPER.findall(v) or found
    return found[-1] if found else ev.name


def read_xplane(path: str) -> TraceView:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    view = TraceView()
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    view.device_ops[plane.name] = [
                        (op_name(ev), ev.start_ns,
                         ev.start_ns + ev.duration_ns)
                        for ev in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                view.host_spans.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events if ev.duration_ns > 0)
    return view


class Tracer:
    """Traces the first whole calls of the window, until ``seconds`` of
    it have passed (at least one call: with ``seconds`` 0, just the
    first)."""

    def __init__(self, directory: str | None, seconds: float):
        self.directory = directory
        self.seconds = seconds
        self.active = False
        self.done = directory is None

    def before_call(self):
        if self.done or self.active:
            return
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # host spans are TraceMes; no Python frames
        jax.profiler.start_trace(self.directory, profiler_options=opts)
        self.active = True

    def after_call(self, elapsed: float):
        if self.active and elapsed >= self.seconds:
            self.stop()

    def stop(self):
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False
            self.done = True

    def view(self) -> TraceView | None:
        if self.directory is None:
            return None
        paths = sorted(glob.glob(os.path.join(
            self.directory, "plugins", "profile", "*", "*.xplane.pb")))
        return read_xplane(paths[-1]) if paths else None
