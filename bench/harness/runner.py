"""One run of one cell: set-up, the measured window, the check, the line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Set-up generates the configuration's data on the device from the seed,
builds the program's objective from it, and makes one warm-up call
(programs come from JAX's persistent cache in ``.jax_cache/`` of the
checkout, or ``$JAX_COMPILATION_CACHE_DIR``).  The cell's driver then
offers its load for ``--seconds``.  After the window the device's peak
memory is read, the program's state is dropped, and every selection of
the window is judged against the plain reference, and the objective's
kernels at the cell's launch shapes (``harness.check``).  A traced run
leaves its profile in ``.bench_trace/<workload>/`` of the checkout.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number
compared with its limit.  Without a TPU, or with fewer chips than the
cell asks for, nothing is measured and the exit code is 2.
"""

from __future__ import annotations

import argparse
import gc
import inspect
import json
import os
import sys
import time
import traceback

from harness import check, spec
from harness.trace import CONTAINERS, Tracer, op_key, opcode, outermost
from harness.view import RunView

TRACE_SECONDS = 0.0         # traced stretch: the window's first call
GIB = float(1 << 30)
LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def log(text: str):
    print(text, file=sys.stderr, flush=True)


def import_program(root: str):
    """Import ``repro`` from this checkout's ``src/``, and only from it."""
    src = os.path.join(root, "src")
    sys.path.insert(0, src)
    import repro

    if not os.path.realpath(repro.__file__).startswith(
            os.path.realpath(src) + os.sep):
        raise ImportError(f"repro came from {repro.__file__}, not {src}")
    return repro


def require_chip(jax, chips: int):
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform})")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devs)}")
    return devs[:chips]


def enable_compile_cache(jax, root: str) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def master_key(jax, seed: int):
    """Key from a seed of any size: low 32 bits, then the rest folded in."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


class Session:
    """The system under test, as one cell drives it."""

    def __init__(self, cell, seed: int):
        import jax

        self.jax = jax
        self.cell = cell
        self.seed = seed
        self.key = master_key(jax, seed)
        self.data = None
        self.obj = None
        self.in_window = False
        self.lowerings = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **kw):
        if self.in_window and event == LOWERING_EVENT:
            self.lowerings += 1

    def open_window(self):
        self.lowerings = 0
        self.in_window = True

    def close_window(self):
        self.in_window = False

    def generate(self):
        gen = self.cell.module("generators", self.cell.config["generator"])
        self.data = gen.generate(self.jax.random.fold_in(self.key, 1),
                                 self.cell.sizes, self.cell.config["data"])
        self.jax.block_until_ready(self.data)

    def build(self, **override):
        """The objective from the configuration's options (``override``
        replaces some of them: the limits probe's other precisions)."""
        import repro.core as core

        spec_ = self.cell.config["objective"]
        cls = getattr(core, spec_["class"])
        accepted = inspect.signature(cls.__init__).parameters
        opts = {}
        for name, v in {**spec_.get("options", {}), **override}.items():
            if name in accepted:
                opts[name] = v
            else:
                log(f"[setup] {spec_['class']} no longer takes {name!r}: "
                    "dropped")
        args = [self.data[a] for a in spec_["args"]]
        self.obj = cls(*args, kmax=int(self.cell.sizes["k"]), **opts)
        return self.obj

    def call_key(self, i: int):
        jax = self.jax
        if i < 0:
            return jax.random.fold_in(jax.random.fold_in(self.key, 3), 0)
        return jax.random.fold_in(jax.random.fold_in(self.key, 2), i)

    def call(self, key):
        from repro.core import select

        return select(self.cell.algo, self.obj, int(self.cell.sizes["k"]),
                      key, **self.cell.options)

    @staticmethod
    def keep(res) -> dict:
        return {"sel_mask": res.sel_mask, "sel_count": res.sel_count,
                "value": res.value,
                "rounds": getattr(res.raw, "rounds", None)}

    def drop_program(self):
        """Free what the window's calls left behind: the objective's
        cached runners and their buffers.  The objective's leaves and
        options stay, in a copy without its caches, for the kernel
        checks."""
        leaves, tree = self.jax.tree_util.tree_flatten(self.obj)
        self.obj = self.jax.tree_util.tree_unflatten(tree, leaves)
        gc.collect()

    def close(self):
        """Drop the program and the data, and stop counting events."""
        self.jax.monitoring.unregister_event_duration_listener(self._on_event)
        self.obj = self.data = None
        gc.collect()


def breakdown(trace) -> dict:
    """The operations that took most device time, loops and calls left
    out (their bodies' operations are listed), and the longest idle gaps
    by what the host was doing in them."""
    plane = sorted(trace.device_ops)[0]
    per_op = {}
    for n, s, e in trace.ops(plane):
        if opcode(n) not in CONTAINERS:
            per_op.setdefault(op_key(n), []).append((s, e))
    per_op = {k: sum(e - s for s, e in outermost(v)) / 1e9
              for k, v in per_op.items()}
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(trace.idle_gaps(plane), key=lambda g: g[0] - g[1])[:10]
    return {"device_ops": [[n, v] for n, v in top],
            "idle_gaps": [[trace.host_at((s + e) / 2), (e - s) / 1e9]
                          for s, e in gaps]}


def traced(line, device, cell, calls, lowerings, view, peaks) -> dict:
    """The cell's per-layer metrics; the device's busy and window
    seconds and the breakdown go into ``device`` and ``line``."""
    run = RunView(cell, calls, lowerings, view, peaks)
    metrics = {}
    for m in cell.per_layer:
        v = cell.module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if view is not None and view.window is not None and view.device_ops:
        busy = [view.busy_ns(p) for p in view.device_ops]
        device.update(busy_s=sum(busy) / len(busy) / 1e9,
                      window_s=(view.window[1] - view.window[0]) / 1e9)
        line["breakdown"] = breakdown(view)
    return metrics


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t_start=None, root=spec.ROOT, chip=require_chip) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse(argv)
    cell = spec.load_cell(args.workload, root)
    import_program(root)
    import jax
    from jax.profiler import TraceAnnotation

    try:
        devices = chip(jax, cell.chips)
    except NoChip as e:
        log(f"bench: {e}; nothing was measured")
        return 2
    kind = devices[0].device_kind
    peaks_all = spec.load_json(os.path.join(cell.bench_dir, "peaks.json"))
    if kind not in peaks_all and args.trace:
        log(f"bench: device kind {kind!r} is not in peaks.json")
        return 2
    log(f"[setup] compile cache: {enable_compile_cache(jax, root)}")

    session = Session(cell, args.seed)
    with TraceAnnotation("bench.generate"):
        session.generate()
    log(f"[setup] data generated at {time.perf_counter() - t_start:.3f}s; "
        f"peak {peak_bytes(devices) / GIB:.4f} GiB")
    session.build()
    trace_dir = (os.path.join(root, ".bench_trace", cell.name)
                 if args.trace else None)
    tracer = Tracer(trace_dir, min(TRACE_SECONDS, args.seconds))
    driver = cell.module("drivers", cell.traffic["driver"])
    t0, calls, e2e = driver.run(session, args.seconds, tracer)
    setup_s = t0 - t_start
    peak = peak_bytes(devices)
    log(f"[window] {len(calls)} calls in {calls[-1].end:.3f}s; "
        f"peak {peak / GIB:.4f} GiB; lowerings {session.lowerings}")
    session.drop_program()

    ref = cell.module("references", cell.config["reference"])
    with TraceAnnotation("bench.check"):
        numbers, f_values = check.selections(cell, session.data, calls, ref)
        numbers.update(check.kernels(cell, session.obj, session.data,
                                     session.key, ref))
    correct, rows = check.judge(numbers, cell.limits["limits"])
    failed = numbers["invalid_calls"]        # raised, or an invalid set

    e2e.update(setup_s=setup_s, peak_hbm_gib=peak / GIB)
    # No valid selection: the run is not correct, and f_value reads 0.
    e2e["f_value"] = sum(f_values) / len(f_values) if f_values else 0.0
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": len(calls), "failed": failed}
    if args.trace:
        metrics = traced(line, device, cell, calls, session.lowerings,
                         tracer.view(), peaks_all[kind])
    else:
        metrics = {}
        for m in cell.end_to_end:
            if m["name"] not in e2e:
                raise KeyError(f"end-to-end metric {m['name']!r} not measured")
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    line.update(metrics=metrics, device=device)
    line["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for n, v, lim in rows:
        log(f"check {n} {v!r} limit {lim!r}")
    print(json.dumps(line), flush=True)
    return 0


def entry(t_start: float) -> int:
    try:
        return main(t_start=t_start)
    except Exception:
        traceback.print_exc()
        return 1
