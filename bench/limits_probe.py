#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/limits_probe.py --workload <name> --seeds 1,2,3 \
        --seconds 10 [--controls bf16,fp8] [--program-precision bf16] [--out F]

For each seed it sets the cell up as a run does, drives a short window
of the program at the cell's own load, and prints one JSON line: the
numbers the program reads (``program``), the numbers each control reads
in the program's place (``control.<lower>``: the plain reference in a
lower precision, ``harness.lowp``), and, with ``--program-precision``,
the kernel numbers of the same objective built with that streamed-operand
precision (``program.<precision>``).  The
benchmark's runs never run a control; this script is how the readings
in ``bench/limits/<workload>.json`` were taken.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from harness import check, runner, spec  # noqa: E402
from harness.trace import Tracer  # noqa: E402


def main(argv=None, root=spec.ROOT, chip=runner.require_chip) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--controls", default="")
    ap.add_argument("--program-precision", default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, root)
    runner.import_program(root)
    import jax

    try:
        chip(jax, cell.chips)
    except runner.NoChip as e:
        runner.log(f"limits_probe: {e}")
        return 2
    runner.enable_compile_cache(jax, root)
    ref = cell.module("references", cell.config["reference"])
    driver = cell.module("drivers", cell.traffic["driver"])
    lowers = [x for x in args.controls.split(",") if x]
    out = open(args.out, "a") if args.out else None
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        s = runner.Session(cell, seed)
        s.generate()
        s.build()
        _, calls, e2e = driver.run(s, args.seconds, Tracer(None, 0))
        s.drop_program()
        rec = {"workload": cell.name, "seed": seed, "calls": len(calls),
               "select_s": e2e.get("select_s")}
        nums, f_values = check.selections(cell, s.data, calls, ref)
        nums.update(check.kernels(cell, s.obj, s.data, s.key, ref))
        rec["program"] = nums
        rec["f_values"] = f_values
        rec["rounds"] = [None if c.out is None or c.out["rounds"] is None
                         else int(c.out["rounds"]) for c in calls]
        if args.program_precision:
            other = s.build(precision=args.program_precision)
            rec[f"program.{args.program_precision}"] = check.kernels(
                cell, other, s.data, s.key, ref)
            del other
        for lower in lowers:
            nums, _ = check.selections(cell, s.data, calls, ref, lower)
            nums.update(check.kernels(cell, None, s.data, s.key, ref, lower))
            rec[f"control.{lower}"] = nums
        rec["seconds"] = time.perf_counter() - t
        line = json.dumps(rec)
        print("PROBE " + line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
        s.close()
        del s, calls
    return 0


if __name__ == "__main__":
    sys.exit(main())
