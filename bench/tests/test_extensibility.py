"""A configuration, a traffic mix, a cell and a per-layer metric are
added with new files and new entries of BENCHMARK.json alone: no file
of the harness, and no file that was there, is edited."""

import json
import os
import shutil

from conftest import make_root, run_cell

METRIC = '''"""Calls the window completed (a test metric)."""


def read(run):
    return float(len(run.completed))
'''


def test_new_config_cell_and_metric(tmp_path, capsys):
    root = make_root(str(tmp_path))
    bench = os.path.join(root, "bench")
    before = {}
    for d, _, files in os.walk(bench):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()

    with open(os.path.join(bench, "configs", "d1-regression.json")) as f:
        cfg = json.load(f)
    cfg.update(name="d1-regression-narrow")
    cfg["sizes"].update(n=2048, k=8, support=16)
    with open(os.path.join(bench, "configs", "d1-regression-narrow.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "greedy.json"), "w") as f:
        json.dump({"driver": "closed_loop", "algo": "greedy", "size": "exact",
                   "options": {}, "launches": {"sweep": {"states": 1}}}, f)
    shutil.copy(os.path.join(bench, "limits", "d1-regression.dash.json"),
                os.path.join(bench, "limits", "d1-regression-narrow.greedy.json"))
    with open(os.path.join(bench, "metrics", "calls_completed.py"), "w") as f:
        f.write(METRIC)

    bpath = os.path.join(root, "BENCHMARK.json")
    with open(bpath) as f:
        b = json.load(f)
    b["configs"].append({"name": "d1-regression-narrow",
                         "source": b["configs"][0]["source"],
                         "file": "bench/configs/d1-regression-narrow.json",
                         "reduced": ["n", "support"], "why": "test"})
    b["workloads"].append({"name": "d1-regression-narrow.greedy",
                           "config": "d1-regression-narrow",
                           "traffic": "greedy", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "calls_completed", "unit": "calls",
                           "better": "higher", "source": "program_counter",
                           "layer": "entry", "moves": "select_s",
                           "workloads": ["d1-regression-narrow.greedy"]})
    with open(bpath, "w") as f:
        json.dump(b, f)

    rc, line = run_cell(root, "d1-regression-narrow.greedy", capsys, trace=1)
    assert rc == 0 and line["correct"] is True
    assert set(line["checks"]) == {"invalid_calls", "value_gap",
                                   "regression_gains_gap"}
    assert line["metrics"]["calls_completed"]["value"] == line["attempted"]
    rc, line = run_cell(root, "d1-regression-narrow.greedy", capsys)
    assert rc == 0 and line["correct"] is True and "select_s" in line["metrics"]

    for p, data in before.items():
        with open(p, "rb") as fh:
            assert fh.read() == data, f"{p} was edited"
