"""Self-test for the doc-lint (scripts/check_docs.py).

Two halves: the real docs must be clean (the CI gate), and a doctored
doc referencing a nonexistent path / flag / module MUST fail — a linter
that never fires is worse than none.  No jax import anywhere in this
path, so the test runs in any lane.
"""

import importlib.util
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

spec = importlib.util.spec_from_file_location(
    "check_docs", REPO / "scripts" / "check_docs.py")
check_docs = importlib.util.module_from_spec(spec)
spec.loader.exec_module(check_docs)


class TestVocabulary:
    def test_known_flags_collected(self):
        flags = check_docs.known_flags()
        # registered by bench/harness/runner.py
        assert "--workload" in flags
        # the allowlist rides along
        assert "--xla_force_host_platform_device_count" in flags


class TestRealDocs:
    def test_readme_and_docs_clean(self):
        files = [REPO / "README.md"] + sorted((REPO / "docs").glob("*.md"))
        assert files, "docs/ vanished?"
        problems = check_docs.lint_files(files)
        assert problems == []


class TestCatchesDrift:
    """The acceptance criterion: a doc referencing a nonexistent
    path/flag/module must FAIL the lint."""

    def _lint_text(self, tmp_path, text):
        doc = tmp_path / "doc.md"
        doc.write_text(text)
        return check_docs.lint_files([doc])

    def test_bogus_path_fails(self, tmp_path):
        probs = self._lint_text(
            tmp_path, "See `core/no_such_module.py` for details.\n")
        assert len(probs) == 1 and "no_such_module.py" in probs[0]

    def test_bogus_symbol_fails(self, tmp_path):
        probs = self._lint_text(
            tmp_path,
            "Entry point: `core/fast.py::definitely_not_a_symbol`.\n")
        assert len(probs) == 1 and "definitely_not_a_symbol" in probs[0]

    def test_bogus_flag_fails(self, tmp_path):
        probs = self._lint_text(
            tmp_path, "Pass `--definitely-not-a-flag` to enable it.\n")
        assert len(probs) == 1 and "--definitely-not-a-flag" in probs[0]

    def test_bogus_module_fails(self, tmp_path):
        probs = self._lint_text(
            tmp_path,
            "```\npython -m benchmarks.no_such_bench --json out.json\n```\n")
        assert len(probs) == 1 and "no_such_bench" in probs[0]

    def test_fenced_bogus_path_fails(self, tmp_path):
        probs = self._lint_text(
            tmp_path, "```\npython examples/not_an_example.py\n```\n")
        assert len(probs) == 1 and "not_an_example.py" in probs[0]

    def test_placeholders_and_artifacts_skipped(self, tmp_path):
        probs = self._lint_text(tmp_path, "\n".join([
            "Writes `~/.cache/repro/tuning.json` and `manifest.json`;",
            "layout `kernels/<name>/{f32,bf16}` with `<cell>.json` rows;",
            "emit keys like `kernels/aopt_gains` are not paths.",
        ]) + "\n")
        assert probs == []

    def test_good_doc_passes(self, tmp_path):
        probs = self._lint_text(tmp_path, "\n".join([
            "Dispatch lives in `core/algorithms.py::select`; run",
            "`bench/run.py --workload d1-design.dash` or",
            "```",
            "python3 chip_smoke.py --chips 4",
            "```",
        ]) + "\n")
        assert probs == []


class TestCLI:
    def test_exit_zero_on_clean_tree(self):
        r = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "check_docs.py")],
            capture_output=True, text=True)
        assert r.returncode == 0, r.stderr

    def test_exit_one_on_bad_doc(self, tmp_path):
        doc = tmp_path / "bad.md"
        doc.write_text("Broken ref: `src/repro/core/gone.py`.\n")
        r = subprocess.run(
            [sys.executable, str(REPO / "scripts" / "check_docs.py"),
             str(doc)],
            capture_output=True, text=True)
        assert r.returncode == 1
        assert "gone.py" in r.stderr
