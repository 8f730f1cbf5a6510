"""Work counts, roofline shares and trace metrics on a small trace.

``recorded_trace.json`` holds a short stretch of a profiled run of the
regression DASH cell at a tiny size, reduced to operations and host
spans (see ``make_recorded``); the metrics are checked on it against
the reduction worked out from the same intervals, and on a synthetic
view whose answers are known exactly.
"""

import json
import os

import pytest

from conftest import run_cell
from harness import roofline, spec
from harness.trace import TraceView, read_xplane, union_length
from harness.view import RunView

HERE = os.path.dirname(os.path.abspath(__file__))
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


_ROOT = []


@pytest.fixture(autouse=True, scope="module")
def _full_root(full_root):
    _ROOT[:] = [full_root]


def _cell(workload):
    """The cell at its own sizes; the pending cells included under
    pytest (``conftest.full_root``)."""
    return spec.load_cell(workload, *_ROOT)


def _view(workload, ops, spans):
    trace = TraceView(device_ops={"/device:TPU:0": ops}, host_spans=spans)
    return RunView(_cell(workload), [], 0, trace, PEAKS)


def test_work_counts_follow_the_declared_launches():
    from harness import launch

    reg = _cell("d1-regression.dash")
    assert launch.kernels(reg) == [("filter", "filter_gains"),
                                   ("sweep", "regression_gains")]
    sh = launch.shape(reg, "filter")
    assert (sh["G"], sh["m"], sh["b"]) == (8, 8, 10)     # 20 rounds of 10
    flops, nbytes = spec.load_module("work", "filter_gains").per_launch(sh)
    d, n = 1000, 1 << 19
    assert flops == 64 * n * (2 * d + 2 * d * 10 + 20)
    assert nbytes == 4 * (d * n + 8 * n + 64 * d * 11 + 64 * n)
    mg = spec.load_module("work", "regression_gains")
    assert mg.per_launch(launch.shape(reg, "sweep")) == (
        2.0 * d * n, 4.0 * (d * n + 2 * n + d))
    des = _cell("d1-design.dash")
    assert launch.kernels(des) == [("sweep", "aopt_gains")]
    assert launch.role_of(des, "filter_gains") is None
    assert launch.role_of(des, "aopt_gains") == "sweep"


def test_declared_launches_match_the_options():
    """The dash mix's declared lattice is the one its options ask for."""
    cell = _cell("d1-regression.dash")
    o, f = cell.options, cell.traffic["launches"]["filter"]
    assert (f["guesses"], f["samples"], f["r"]) == (
        o["n_guesses"] * len(o.get("alphas") or [o["alpha"]]),
        o["n_samples"], o["r"])


@pytest.mark.parametrize("kernel,workload", [
    ("filter_gains", "d1-regression.dash"),
    ("regression_gains", "d1-regression.dash"),
    ("aopt_gains", "d1-design.dash")])
def test_roofline_is_100_at_the_least_time(kernel, workload):
    from harness import launch

    work = spec.load_module("work", kernel)
    cell = _cell(workload)
    flops, nbytes = work.per_launch(
        launch.shape(cell, launch.role_of(cell, kernel)))
    least_ns = 1e9 * max(flops / PEAKS["flops_per_s"],
                         nbytes / PEAKS["hbm_bytes_per_s"])
    name = work.HLO_NAMES[0]
    ops = [(f"{name}.{i}", 10_000 + i * 2 * least_ns,
            10_000 + i * 2 * least_ns + least_ns) for i in range(3)]
    ops.append(("fusion.7", 0, 5_000))
    run = _view(workload, ops, [("bench.select", 0, 10_000 + 6 * least_ns)])
    assert roofline.share(run, kernel) == pytest.approx(100.0)
    slow = [(n, s, s + 2 * (e - s)) for n, s, e in ops[:3]]
    run = _view(workload, slow, [("bench.select", 0, 1e12)])
    assert roofline.share(run, kernel) == pytest.approx(50.0)


def test_device_metrics_on_a_synthetic_view():
    idle = spec.load_module("metrics", "device_idle_pct")
    pallas = spec.load_module("metrics", "pallas_busy_pct")
    ops = [("fusion.1", 0, 100), ("filter_gains_pallas.3", 150, 350),
           ("copy.2", 300, 400), ("top-k.9", 900, 1000)]
    run = _view("d1-regression.dash", ops, [("bench.select", 0, 1000),
                                            ("dispatch", 400, 900)])
    assert idle.read(run) == pytest.approx(100 * (1 - 450 / 1000))
    assert pallas.read(run) == pytest.approx(100 * 200 / 450)
    from harness.runner import breakdown

    b = breakdown(run.trace)
    assert b["device_ops"][0] == ["filter_gains_pallas", 200 / 1e9]
    assert b["idle_gaps"][0] == ["dispatch", 500 / 1e9]


def test_launches_under_vmap_count_for_their_kernel():
    from harness.trace import base_name

    assert base_name("vmap_jit_filter_gains_pallas__.2") == "filter_gains_pallas"
    assert base_name("aopt_filter_gains_pallas.7") == "aopt_filter_gains_pallas"
    assert base_name("jit_convert_element_type.1") == "jit_convert_element_type"
    ops = [("fusion.1", 0, 100), ("vmap_jit_regression_gains_pallas__.1", 100, 300)]
    run = _view("d1-regression.dash", ops, [("bench.select", 0, 400)])
    pallas = spec.load_module("metrics", "pallas_busy_pct")
    assert pallas.read(run) == pytest.approx(100 * 200 / 300)
    assert roofline.share(run, "regression_gains") is not None


def test_operations_named_by_their_hlo_text():
    """A TPU profile names each operation by its whole HLO text; the
    kernels are found by the instruction's name, a launch and an event
    nested in it count once, and the breakdown lists the operations
    inside a loop, not the loop."""
    loop = ("%while.2 = (s32[]{:T(128)}, f32[8,8]{1,0:T(8,128)S(1)}) "
            "while((s32[]{:T(128)}, f32[8,8]) %tuple.5), condition=%c.1")
    launch = ("%filter_gains_pallas.8 = f32[64,1,1048576]{2,1,0:T(1,128)S(1)} "
              "custom-call(%get-tuple-element.24, %copy-done.3), "
              'custom_call_target="tpu_custom_call"')
    fusion = "%fusion.7 = f32[8]{0:T(128)} fusion(%param.1), kind=kLoop"
    ops = [(loop, 0, 1000), (fusion, 10, 100), (launch, 100, 300),
           (launch, 120, 280), (launch, 400, 600), (fusion, 600, 700)]
    run = _view("d1-regression.dash", ops, [("bench.select", 0, 1000)])
    assert spec.load_module("metrics", "pallas_busy_pct").read(run) == (
        pytest.approx(100 * 400 / 1000))
    assert roofline.share(run, "filter_gains") == pytest.approx(
        roofline.share(_view("d1-regression.dash",
                             [("filter_gains_pallas.1", 0, 200),
                              ("filter_gains_pallas.1", 300, 500)],
                             [("bench.select", 0, 1000)]), "filter_gains"))
    from harness.runner import breakdown

    b = breakdown(run.trace)
    assert b["device_ops"] == [["filter_gains_pallas", 400 / 1e9],
                               ["fusion.7", 190 / 1e9]]


def test_kernels_the_trace_does_not_name_give_no_reading(capsys):
    """A trace whose kernel launches carry other names (a bare
    ``custom-call``) gives no share, rather than 0%, and says why."""
    ops = [("fusion.1", 0, 100), ("custom-call.3", 150, 350)]
    run = _view("d1-regression.dash", ops, [("bench.select", 0, 1000)])
    assert spec.load_module("metrics", "pallas_busy_pct").read(run) is None
    assert roofline.share(run, "filter_gains") is None
    err = capsys.readouterr().err
    assert "filter_gains_pallas" in err and "pallas_busy_pct" in err


def test_metrics_on_the_recorded_trace():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        rec = json.load(f)
    ops = [tuple(o) for o in rec["device_ops"]]
    run = _view(rec["workload"], ops, [tuple(s) for s in rec["host_spans"]])
    w = run.trace.window
    busy = union_length((s, e) for _, s, e in run.trace.ops())
    idle = spec.load_module("metrics", "device_idle_pct").read(run)
    assert idle == pytest.approx(100 * (1 - busy / (w[1] - w[0])))
    assert 0.0 <= idle <= 100.0
    for m, want in rec["metrics"].items():
        got = spec.load_module("metrics", m).read(run)
        if want is None:
            assert got is None, m
        else:
            assert got == pytest.approx(want, rel=1e-9), m
            assert 0.0 <= got <= 100.0, m


def test_xplane_is_read_from_a_cpu_profile(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    from harness.trace import Tracer

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    t = Tracer(str(tmp_path), 0.0)
    t.before_call()
    with TraceAnnotation("bench.select"):
        f(x).block_until_ready()
    t.after_call(1.0)
    view = t.view()
    assert view.window is not None
    assert view.device_ops == {}          # the CPU has no TPU plane
    run = RunView(_cell("d1-regression.dash"), [], 0, view, PEAKS)
    assert spec.load_module("metrics", "device_idle_pct").read(run) is None


def test_a_metric_that_finds_nothing_is_left_out(tiny_root, capsys):
    rc, line = run_cell(tiny_root, "d1-regression.dash", capsys, trace=1)
    assert rc == 0
    # On the CPU there is no device plane: no device metric, no roofline.
    for m in ("device_idle_pct", "pallas_busy_pct", "filter_gains_roofline"):
        assert m not in line["metrics"]
    assert "adaptive_rounds" in line["metrics"]


def make_recorded(xplane, workload, out, ms=60.0):
    """Cut ``ms`` milliseconds of a profile into ``recorded_trace.json``
    with the metrics the harness reads from that stretch.

        python bench/tests/test_work_and_metrics.py <xplane.pb> <workload>

    A chip profile's TPU plane is taken as it is.  A CPU profile has no
    device plane: its XLA operations (host events that carry an
    ``hlo_op``) stand in for the device's, so the reduction can be
    checked on a real program's intervals.
    """
    from jax.profiler import ProfileData

    view = read_xplane(xplane)
    if view.device_ops:
        ops = view.device_ops[sorted(view.device_ops)[0]]
    else:
        ops = []
        for plane in ProfileData.from_file(xplane).planes:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0 and any(
                            k == "hlo_op" for k, _ in ev.stats):
                        ops.append((ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns))
    t0 = min(s for _, s, _ in ops)
    t1 = t0 + ms * 1e6
    cut = [(n, s, min(e, t1)) for n, s, e in ops if s < t1]
    spans = [("bench.select", t0, t1)] + [
        (n, s, e) for n, s, e in view.host_spans
        if s < t1 and e > t0 and n != "bench.select" and 5e4 < e - s < t1 - t0]
    run = _view(workload, cut, spans)
    metrics = {}
    for m in run.cell.per_layer:
        if m["source"] == "device_trace":
            metrics[m["name"]] = spec.load_module("metrics", m["name"]).read(run)
    with open(out, "w") as f:
        json.dump({"workload": workload, "device_ops": cut,
                   "host_spans": spans, "metrics": metrics}, f)


if __name__ == "__main__":
    import sys

    make_recorded(sys.argv[1], sys.argv[2],
                  os.path.join(HERE, "recorded_trace.json"))
