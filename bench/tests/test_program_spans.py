"""The metrics that read the program's own scopes and spans.

``sampler_busy_pct`` and ``state_update_busy_pct`` read the op-name
paths of device operations (``harness.scopes``); ``entry_idle_pct``
reads the host spans the program opens.  They are checked on synthetic
views whose answers are known exactly, and on ``recorded_scopes.json``:
a stretch of a traced design call on a TPU v5e, with each operation's
op-name path (see ``make_recorded``).
"""

import json
import os

import pytest

from conftest import make_root
from harness import scopes, spec
from harness.trace import TraceView, union_length
from harness.view import RunView

HERE = os.path.dirname(os.path.abspath(__file__))
PLANE = "/device:TPU:0"
ROUND = "jit(run)/vmap()/while/body/closed_call/repro.round"
SAMPLE = ROUND + "/repro.estimate/vmap(repro.sample)"
ADD = ROUND + "/repro.add_set"

_ROOT = []


@pytest.fixture(autouse=True, scope="module")
def _full_root(full_root):
    _ROOT[:] = [full_root]


def _view(ops, spans, paths=True):
    """A run of the design cell over ``ops`` [(name, start, end, path)]
    and host ``spans``; with ``paths`` false, a trace whose paths were
    never read (as a parent's)."""
    trace = TraceView(device_ops={PLANE: [o[:3] for o in ops]},
                      host_spans=spans)
    if paths:
        trace.op_paths = {PLANE: list(ops)}
    cell = spec.load_cell("d1-design.dash", *_ROOT)
    return RunView(cell, [], 0, trace, {})


def _metric(name):
    return spec.load_module("metrics", name)


def test_scope_is_one_component_without_its_transformations():
    assert scopes.components("jit(f)/vmap(jit(repro.sample))/sort") == [
        "f", "repro.sample", "sort"]
    assert scopes.in_scope(SAMPLE + "/sort", "repro.sample")
    assert scopes.in_scope(SAMPLE + "/sort", "repro.round")
    assert not scopes.in_scope(ROUND + "/repro.samplers/sort",
                               "repro.sample")
    assert not scopes.scoped("jit(convert_element_type)/convert")


def test_a_nested_scope_counts_once():
    """An operation under ``repro.round/.../repro.sample`` counts for
    the sampler once; an event nested in it adds nothing; a loop that
    holds scoped operations is not itself counted."""
    loop = "%while.3 = (s32[]) while((s32[]) %t), condition=%c, body=%b"
    ops = [(loop, 0, 1000, ROUND + "/repro.filter/while"),
           ("%sort.32 = f32[8] sort(f32[8] %p)", 100, 300, SAMPLE + "/sort"),
           ("%fusion.2 = f32[8] fusion(f32[8] %p)", 150, 250,
            SAMPLE + "/sort"),
           ("%fusion.7 = f32[8] fusion(f32[8] %q)", 300, 700, ADD + "/dot"),
           ("%copy.1 = f32[8] copy(f32[8] %q)", 800, 1000, ROUND)]
    run = _view(ops, [("bench.select", 0, 1000)])
    assert _metric("sampler_busy_pct").read(run) == pytest.approx(20.0)
    assert _metric("state_update_busy_pct").read(run) == pytest.approx(40.0)
    assert scopes.scope_ns(ops, "repro.round") == 800
    assert scopes.scope_ns(ops, "repro.filter") == 0     # the loop alone


def test_busy_time_is_a_union_not_a_sum():
    ops = [("%sort.1 = f32[8] sort(f32[8] %p)", 0, 400, SAMPLE + "/sort"),
           ("%sort.2 = f32[8] sort(f32[8] %p)", 200, 600, SAMPLE + "/sort"),
           ("%fusion.1 = f32[8] fusion(f32[8] %p)", 700, 800, ADD)]
    run = _view(ops, [("bench.select", 0, 1000)])
    busy = union_length((s, e) for _, s, e, _ in ops)
    assert busy == 700
    assert _metric("sampler_busy_pct").read(run) == pytest.approx(
        100 * 600 / 700)


def test_operations_outside_the_window_are_clipped():
    ops = [("%sort.1 = f32[8] sort(f32[8] %p)", 0, 400, SAMPLE + "/sort"),
           ("%fusion.1 = f32[8] fusion(f32[8] %p)", 400, 600, ADD)]
    run = _view(ops, [("bench.select", 300, 600)])
    assert _metric("sampler_busy_pct").read(run) == pytest.approx(
        100 * 100 / 300)


def test_entry_idle_is_the_idle_time_inside_the_program_spans():
    """The device is idle over [100, 300) and [600, 1000); the program's
    spans cover [0, 400); so 200 of the idle 600 are the program's."""
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 100, ADD),
           ("%sort.1 = f32[8] sort(f32[8] %p)", 300, 600, SAMPLE + "/sort")]
    spans = [("bench.select", 0, 1000), ("repro.select", 0, 400),
             ("repro.dash.guesses", 50, 150), ("repro.dash.lattice", 150, 350),
             ("block_until_ready", 400, 1000)]
    run = _view(ops, spans)
    entry = _metric("entry_idle_pct").read(run)
    idle = _metric("device_idle_pct").read(run)
    assert entry == pytest.approx(20.0)
    assert idle == pytest.approx(60.0)
    assert entry <= idle


def test_a_trace_without_the_programs_marks_gives_no_reading(capsys):
    """A parent's trace: no ``repro.*`` span, no path with a scope."""
    ops = [("%fusion.1 = f32[8] fusion(f32[8] %p)", 0, 100,
            "jit(run)/vmap()/while/body/dot_general"),
           ("%sort.1 = f32[8] sort(f32[8] %p)", 300, 600, "")]
    run = _view(ops, [("bench.select", 0, 1000), ("dispatch", 100, 300)])
    for m in ("sampler_busy_pct", "state_update_busy_pct", "entry_idle_pct"):
        assert _metric(m).read(run) is None, m
    err = capsys.readouterr().err
    for m in ("sampler_busy_pct", "state_update_busy_pct", "entry_idle_pct"):
        assert m in err
    # No profile on disk for this cell either: still no reading.
    run = _view(ops, [("bench.select", 0, 1000)], paths=False)
    assert scopes.trace_file(run) is None
    assert _metric("sampler_busy_pct").read(run) is None


def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(num, value):
    """One protobuf field: an int as a varint, bytes or str
    length-delimited."""
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _xplane(name, ops, stats=("flops", "tf_op")):
    """An ``XPlane`` whose event metadata give each op's path as the
    ``tf_op`` stat (by string, or by reference to a stat name)."""
    body = _field(2, name)
    for i, stat in enumerate(stats, 1):
        body += _field(5, _field(1, i) + _field(2, _field(1, i) +
                                                _field(2, stat)))
    for i, (op, path, by_ref) in enumerate(ops, 1):
        stat = _field(1, stats.index("tf_op") + 1)
        if by_ref:
            ref = len(stats) + i
            body += _field(5, _field(1, ref) + _field(2, _field(2, path)))
            stat += _field(7, ref)
        else:
            stat += _field(5, path)
        flops = _field(1, 1) + _field(4, 7) + b"\x11" + bytes(8)  # fixed64
        meta = _field(1, i) + _field(2, op) + _field(5, flops) + _field(
            5, stat)
        body += _field(4, _field(1, i) + _field(2, meta))
    body += _field(3, _field(2, "XLA Ops"))          # a line, skipped
    return body


def test_paths_are_decoded_from_the_profiles_event_metadata(tmp_path):
    sort = "%sort.32 = (f32[8]) sort(f32[8] %p)"
    fusion = "%fusion.7 = f32[8] fusion(f32[8] %q), kind=kLoop"
    space = _field(1, _xplane("/host:CPU", [("host op", "x/y:", False)]))
    space += _field(1, _xplane(PLANE, [(sort, SAMPLE + "/top_k:", False),
                                       (fusion, ADD + "/dot:", True)]))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(space)
    assert scopes.op_paths_by_plane(str(path)) == {
        PLANE: {sort: SAMPLE + "/top_k", fusion: ADD + "/dot"}}


def test_metrics_on_the_recorded_scopes():
    with open(os.path.join(HERE, "recorded_scopes.json")) as f:
        rec = json.load(f)
    ops = [tuple(o) for o in rec["device_ops"]]
    run = _view(ops, [tuple(s) for s in rec["host_spans"]])
    for m, want in rec["metrics"].items():
        got = _metric(m).read(run)
        assert got == pytest.approx(want, rel=1e-9), m
        assert 0.0 < got < 100.0, m
    assert (_metric("entry_idle_pct").read(run)
            <= _metric("device_idle_pct").read(run))
    summary = scopes.summary(scopes.op_paths(run)[PLANE])
    assert summary["scoped"] <= summary["busy"]
    assert summary["repro.sample"] <= summary["repro.round"]


def make_recorded(xplane, out, ms=40.0):
    """Cut ``ms`` milliseconds from the start of the first traced call
    of a chip profile into ``recorded_scopes.json``, with each device
    operation's op-name path and the metrics read from that stretch.

        python bench/tests/test_program_spans.py <xplane.pb>
    """
    from harness.trace import CALL_SPAN, read_xplane

    view = read_xplane(xplane)
    w0 = view.window[0]
    w1 = w0 + ms * 1e6
    plane, ops = sorted(scopes.read_paths(xplane).items())[0]
    cut = [(n, s, min(e, w1), p) for n, s, e, p in ops
           if s < w1 and e > w0]
    spans = [(CALL_SPAN, w0, w1)] + [
        (n, s, e) for n, s, e in view.host_spans
        if n.startswith(scopes.PREFIX) and s < w1 and e > w0]
    run = _view(cut, spans)
    metrics = {m: _metric(m).read(run) for m in (
        "sampler_busy_pct", "state_update_busy_pct", "entry_idle_pct",
        "device_idle_pct")}
    with open(out, "w") as f:
        json.dump({"workload": "d1-design.dash", "plane": plane,
                   "device_ops": cut, "host_spans": spans,
                   "metrics": metrics}, f)


if __name__ == "__main__":
    import sys
    import tempfile

    _ROOT[:] = [make_root(tempfile.mkdtemp(), tiny=False)]
    make_recorded(sys.argv[1], os.path.join(HERE, "recorded_scopes.json"))
