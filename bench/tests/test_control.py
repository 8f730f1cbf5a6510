"""The control of ``correct`` fails the limits; the program passes them.

The configurations state float32 at the TPU's default precision, so the
control is the next precision down.  Where the program has that path of
its own, the control is the program with it switched on: the objective
built with ``precision="bf16"`` (X and the per-guess solves streamed in
bfloat16) drives the window, and its numbers are judged.  Where that
path computes what the float32 one does (a product at default precision
is one bfloat16 pass already), the control is the plain reference in
float8 e4m3 (``harness.lowp``) in the program's place.  Here at a size a
test run holds, on the CPU; on the chip at the cell's own size with
``bench/limits_probe.py`` (readings in PERF.md).
"""

import pytest

from conftest import cpu_chip

CONTROL = {"d1-design.dash": ("program", "bf16"),
           "d1-regression.dash": ("reference", "fp8")}


@pytest.mark.parametrize("workload", ["d1-regression.dash", "d1-design.dash"])
@pytest.mark.parametrize("seed", [3, 2147483901, 5000000017])
def test_control_fails_and_program_passes(tiny_root, workload, seed):
    import jax

    from harness import check, runner, spec
    from harness.trace import Tracer

    cell = spec.load_cell(workload, tiny_root)
    runner.import_program(tiny_root)
    cpu_chip(jax, cell.chips)
    s = runner.Session(cell, seed)
    driver = cell.module("drivers", cell.traffic["driver"])
    ref = cell.module("references", cell.config["reference"])
    limits = cell.limits["limits"]

    def window(**override):
        s.build(**override)
        _, calls, _ = driver.run(s, 0.3, Tracer(None, 0))
        s.drop_program()
        return calls

    try:
        s.generate()
        calls = window()
        nums, _ = check.selections(cell, s.data, calls, ref)
        nums.update(check.kernels(cell, s.obj, s.data, s.key, ref))
        assert check.judge(nums, limits)[0], nums

        kind, lower = CONTROL[workload]
        if kind == "program":
            calls = window(precision=lower)
            ctl, _ = check.selections(cell, s.data, calls, ref)
            ctl.update(check.kernels(cell, s.obj, s.data, s.key, ref))
        else:
            ctl, _ = check.selections(cell, s.data, calls, ref, lower)
            ctl.update(check.kernels(cell, None, s.data, s.key, ref, lower))
        assert not check.judge(ctl, limits)[0], ctl
    finally:
        s.close()
