"""Device operations of a traced run, with the program's scopes.

The program names the phases of its compiled selection loop with
``jax.named_scope``: ``repro.round`` (one round), ``repro.estimate``
(the set-gain estimate), ``repro.filter`` (the filter loop),
``repro.sample`` (the Gumbel-top-k sampler) and ``repro.add_set`` (the
objective's state update).  Every XLA operation they emit carries the
scopes in its op-name path (``jit(run)/vmap()/while/body/closed_call/
repro.round/repro.estimate/vmap(repro.sample)/top_k``).

A TPU profile keeps that path as the ``tf_op`` stat of each operation's
event metadata, which ``jax.profiler.ProfileData`` does not show.  So
this module reads the operations' names and intervals as ``harness.trace``
does, with ``ProfileData``, and their paths from the same ``.xplane.pb``
(``<root>/.bench_trace/<workload>/``, where ``runner.py`` has the
profiler write it) by decoding the few fields of the profile's protobuf
it needs (``XSpace.planes``, ``XPlane.event_metadata`` and
``stat_metadata``), with the standard library.  An operation is matched
to its metadata by name: its whole HLO text.

A scope matches one component of a path, with the transformations JAX
wraps round it (``vmap(...)``, ``jit(...)``) taken off, so a scope nested
in another counts for both.

    cd bench && python3 -m harness.scopes <.xplane.pb>

prints the device time in each scope, the time no scope covers, and the
operations that take most of it.
"""

from __future__ import annotations

import glob
import os
import re
import sys

from harness.trace import (
    CONTAINERS,
    DEVICE_PREFIX,
    OPS_LINE,
    op_key,
    opcode,
    union_length,
)

PREFIX = "repro."
SCOPES = ("repro.round", "repro.estimate", "repro.filter", "repro.sample",
          "repro.add_set")
PATH_STAT = "tf_op"
_WRAPPED = re.compile(r"^[A-Za-z_]\w*\((.*)\)$")


def components(path: str) -> list:
    """The path's components, each without the transformations round it
    (``vmap(jit(repro.sample))`` -> ``repro.sample``)."""
    out = []
    for c in path.split("/"):
        m = _WRAPPED.match(c)
        while m:
            c = m.group(1)
            m = _WRAPPED.match(c)
        out.append(c)
    return out


def in_scope(path: str, scope: str) -> bool:
    return scope in components(path)


def scoped(path: str) -> bool:
    """Whether any of the program's scopes is on the path."""
    return any(c.startswith(PREFIX) for c in components(path))


def _varint(buf, i):
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: a varint as an
    int, a length-delimited field as a memoryview, fixed-width fields as
    None."""
    i = 0
    while i < len(buf):
        tag, i = _varint(buf, i)
        kind = tag & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            value, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} in a profile")
        yield tag >> 3, value


def _map_entry(buf):
    entry = dict(_fields(buf))
    return entry.get(1, 0), entry.get(2, b"")


def _plane_paths(plane) -> tuple:
    """(plane name, {operation name: op-name path}) of one ``XPlane``."""
    name, events, stat_names = "", [], {}
    for f, v in _fields(plane):
        if f == 2:
            name = bytes(v).decode()
        elif f == 4:
            events.append(_map_entry(v)[1])
        elif f == 5:
            key, meta = _map_entry(v)
            stat_names[key] = next((bytes(x).decode() for g, x in
                                    _fields(meta) if g == 2), "")
    if not name.startswith(DEVICE_PREFIX):
        return name, {}
    paths = {}
    for meta in events:
        op, path = "", ""
        for f, v in _fields(meta):
            if f == 2:
                op = bytes(v).decode()
            elif f == 5:
                stat = dict(_fields(v))
                if stat_names.get(stat.get(1)) == PATH_STAT:
                    path = (bytes(stat[5]).decode() if 5 in stat
                            else stat_names.get(stat.get(7), ""))
        # Two programs' operations with one HLO text and two paths:
        # neither path can be told for that name.
        paths[op] = "" if paths.get(op, path) != path else path
    return name, {op: p.rstrip(":") for op, p in paths.items()}


def op_paths_by_plane(path: str) -> dict:
    """{device plane: {operation name: op-name path}} of a profile."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field == 1:
            name, paths = _plane_paths(plane)
            if paths:
                out[name] = paths
    return out


def read_paths(path: str) -> dict:
    """{device plane: [(name, start_ns, end_ns, op-name path)]} of the
    ``XLA Ops`` lines of a profile."""
    from jax.profiler import ProfileData

    names = op_paths_by_plane(path)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        known = names.get(plane.name, {})
        for line in plane.lines:
            if line.name == OPS_LINE:
                out[plane.name] = [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     known.get(ev.name, "")) for ev in line.events]
    return out


def trace_file(run):
    """The newest ``.xplane.pb`` of the run's traced stretch, or None."""
    root = os.path.dirname(run.cell.bench_dir)
    found = sorted(glob.glob(os.path.join(
        root, ".bench_trace", run.cell.name, "plugins", "profile", "*",
        "*.xplane.pb")))
    return found[-1] if found else None


def clip(ops, window):
    """The operations [(name, start, end, path)] cut to ``window``."""
    w0, w1 = window
    return [(n, max(s, w0), min(e, w1), p) for n, s, e, p in ops
            if min(e, w1) > max(s, w0)]


def op_paths(run):
    """{plane: [(name, start, end, path)]} of the run's traced window,
    clipped to it; None where the run has no trace.  Read once per run
    (kept on the trace as ``op_paths``; a view built by hand may carry
    its own)."""
    t = run.trace
    if t is None or t.window is None:
        return None
    raw = getattr(t, "op_paths", None)
    if raw is None:
        path = trace_file(run)
        raw = read_paths(path) if path else {}
        t.op_paths = raw
    return {plane: clip(ops, t.window) for plane, ops in raw.items()}


def leaves(ops):
    """The operations that hold no other (loops and calls left out)."""
    return [o for o in ops if opcode(o[0]) not in CONTAINERS]


def scope_ns(ops, scope: str) -> float:
    """Device time in ``scope``: the union of its leaf operations'
    intervals, so nested and overlapping events count once."""
    return union_length((s, e) for _, s, e, p in leaves(ops)
                        if in_scope(p, scope))


def busy_pct(run, scope: str, metric: str):
    """``scope``'s share (%) of the device's busy time, over the chips
    used; None, with a note, where the trace shows none of the program's
    scopes."""
    planes = op_paths(run)
    if not planes:
        return None
    if not any(scoped(p) for ops in planes.values() for *_, p in ops):
        run.note(f"{metric}: no traced device operation carries a "
                 f"{PREFIX}* scope in its op-name path")
        return None
    busy = inside = 0.0
    for ops in planes.values():
        busy += union_length((s, e) for _, s, e, _ in ops)
        inside += scope_ns(ops, scope)
    return 100.0 * inside / busy


def summary(ops) -> dict:
    """Seconds of device time per scope, the busy time and the part of
    it in any scope, and the unscoped leaf operations that take most."""
    lv = leaves(ops)
    out = {s: scope_ns(ops, s) / 1e9 for s in SCOPES}
    out["busy"] = union_length((s, e) for _, s, e, _ in ops) / 1e9
    out["scoped"] = union_length(
        (s, e) for _, s, e, p in lv if scoped(p)) / 1e9
    rest = {}
    for n, s, e, p in lv:
        if not scoped(p):
            rest[op_key(n)] = rest.get(op_key(n), 0.0) + (e - s) / 1e9
    out["unscoped_ops"] = sorted(rest.items(), key=lambda kv: -kv[1])[:10]
    return out


if __name__ == "__main__":
    from harness.trace import read_xplane

    window = read_xplane(sys.argv[1]).window
    for plane, ops in sorted(read_paths(sys.argv[1]).items()):
        print(plane)
        for k, v in summary(clip(ops, window) if window else ops).items():
            print(f"  {k}: {v}")
