"""Every cell runs end to end on the CPU at a tiny size and is correct;
without a chip the command measures nothing."""

import os
import shutil
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, run_cell

CELLS = ["d1-regression.dash", "d1-design.dash"]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(tiny_root, capsys, workload):
    from harness import spec

    rc, line = run_cell(tiny_root, workload, capsys)
    assert rc == 0
    assert line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    cell = spec.load_cell(workload, tiny_root)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert list(line)[-1] == "checks"
    assert set(line["checks"]) >= {"invalid_calls", "value_gap"}
    assert any(k.endswith("_gap") and k != "value_gap" for k in line["checks"])


def test_traced_run_reports_counters(tiny_root, capsys):
    rc, line = run_cell(tiny_root, "d1-design.dash", capsys, trace=1)
    assert rc == 0 and line["correct"] is True
    # DASH's runners are cached on the objective: nothing lowers again.
    assert line["metrics"]["lowerings_per_select"]["value"] == 0.0
    assert line["metrics"]["adaptive_rounds"]["value"] >= 1.0


def _run(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "bench/run.py", *args], cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=300)


def test_refuses_without_a_chip():
    p = _run(ROOT, "--workload", "d1-design.dash", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == 2
    assert p.stdout.strip() == ""


def test_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(tmp_path, "--workload", "d1-design.dash", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
