"""Fixtures for the benchmark's own tests.

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

The repository's test suite does not collect ``bench/``; run these
explicitly.  They run the harness on the CPU at tiny sizes, from a copy
of ``bench/`` and ``BENCHMARK.json`` in a temporary root, with the
harness's look for a chip replaced by the CPU device.
"""

import json
import os
import shutil
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

TINY = {
    "d1-regression": {"d": 64, "n": 4096, "k": 16, "support": 32},
    "d1-design": {"d": 32, "n": 2048, "k": 8},
}


def _edit_json(path, fn):
    with open(path) as f:
        doc = json.load(f)
    fn(doc)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def add_pending(doc):
    """BENCHMARK.json with the cells of ``pending_cells.json`` added."""
    with open(os.path.join(BENCH, "tests", "pending_cells.json")) as f:
        pend = json.load(f)
    doc["configs"] += pend["configs"]
    doc["workloads"] += pend["workloads"]
    doc["per_layer"] += pend["per_layer"]
    names = [w["name"] for w in pend["workloads"]]
    for m in doc["per_layer"]:
        if m["name"] in pend["also_reports"]:
            m["workloads"] = m["workloads"] + names


def make_root(base, tiny=True):
    """A checkout-like root: the benchmark's files with the pending cells
    added, tiny configurations unless ``tiny`` is false, the program's
    src/."""
    shutil.copytree(BENCH, os.path.join(base, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), base)
    _edit_json(os.path.join(base, "BENCHMARK.json"), add_pending)
    os.symlink(os.path.join(ROOT, "src"), os.path.join(base, "src"))
    if not tiny:
        return base
    for name, sizes in TINY.items():
        def shrink(doc, sizes=sizes):
            doc["sizes"] = dict(sizes)
            doc["data"]["chunk"] = 1024
        _edit_json(os.path.join(base, "bench", "configs", f"{name}.json"),
                   shrink)
    # The CPU stands in for the chip here; it needs a row of the table.
    _edit_json(os.path.join(base, "bench", "peaks.json"),
               lambda doc: doc.update(cpu=doc["TPU v5 lite"]))
    return base


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("root")))


@pytest.fixture(scope="session")
def full_root(tmp_path_factory):
    """The benchmark at its own sizes, with the pending cells."""
    return make_root(str(tmp_path_factory.mktemp("full")), tiny=False)


def cpu_chip(jax, chips):
    return jax.devices()[:chips]


def run_cell(root, workload, capsys, seed=7, seconds=1.0, trace=0):
    """Run one cell through the harness; return (exit code, result)."""
    from harness import runner

    rc = runner.main(["--workload", workload, "--seed", str(seed),
                      "--seconds", str(seconds), "--trace", str(trace)],
                     root=root, chip=cpu_chip)
    out = capsys.readouterr().out.strip().splitlines()
    return rc, (json.loads(out[-1]) if out else None)
