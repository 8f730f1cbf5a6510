#!/usr/bin/env python3
"""Doc-lint: keep README.md and docs/*.md honest against the tree.

Every backticked token in the prose that LOOKS like a repo artifact is
verified to exist:

  * **paths** — ``core/fast.py``, ``src/repro/kernels/``,
    ``core/algorithms.py::select`` (the ``::symbol`` suffix is
    additionally grepped for inside the resolved file).
    Bare basenames (``dash.py``) resolve anywhere in the tree; relative
    paths also resolve under ``src/`` and ``src/repro/`` (the docs
    conventionally drop those prefixes).
  * **CLI flags** — ``--flag`` tokens validated against the union of
    every ``add_argument("--...")`` in the repo's Python files, plus a
    small allowlist of external flags (XLA, pip, pytest).
  * **``python -m`` modules** — dotted module paths must resolve to a
    file under the repo (``repro.launch.dryrun`` →
    ``src/repro/launch/dryrun.py``).

The linter parses the tree and imports nothing of it, so it runs
without jax.  Fenced code blocks are scanned for ``python -m`` modules
and ``*.py`` path arguments (commands must stay runnable);
``--flag`` validation applies to inline backticks only, where a flag is
a deliberate reference rather than incidental shell text.

Tokens containing placeholders (``<name>``, ``{f32,bf16}``, ``*``) are
skipped.  Exit status 1 lists every violation; the pytest self-test
(tests/test_check_docs.py) pins that a doc referencing a nonexistent
path, flag or module fails.

Usage:  python scripts/check_docs.py [files...]
        (no args: README.md + docs/*.md)
"""

from __future__ import annotations

import re
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent

#: Extensions that mark a backticked token as a file reference.
_PATH_EXTS = (".py", ".md", ".sh", ".yml", ".yaml", ".json", ".toml",
              ".txt", ".cfg", ".ini")

#: External flags the repo's argparse registry can't know about.
_FLAG_ALLOWLIST = {
    "--xla_force_host_platform_device_count",
    "--pre", "--upgrade", "--timeout", "--timeout-method",
    "--cov", "--tb",
}

_INLINE_CODE = re.compile(r"`([^`\n]+)`")
_FENCE = re.compile(r"^(```|~~~)")
_SYMBOL = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")
_MODULE = re.compile(r"python[0-9.]*\s+-m\s+([A-Za-z_][\w.]*)")
_ADD_ARG = re.compile(r"add_argument\(\s*[\"'](--[A-Za-z0-9][\w-]*)[\"']")


def known_flags(repo: Path = REPO) -> set[str]:
    """Every --flag any repo script registers with argparse."""
    flags = set(_FLAG_ALLOWLIST)
    for py in repo.rglob("*.py"):
        if ".git" in py.parts:
            continue
        try:
            flags.update(_ADD_ARG.findall(py.read_text()))
        except OSError:  # pragma: no cover
            continue
    return flags


#: Runtime-generated artifacts the docs legitimately name although they
#: are not tracked in the tree.
_GENERATED = re.compile(
    r"^(manifest\.json|tuning\.json|\.jax_cache)$")


def _is_placeholder(tok: str) -> bool:
    return any(ch in tok for ch in "<>{}*")


def _resolve_path(tok: str, repo: Path) -> Path | None:
    """Resolve a doc path against the tree, or None if it doesn't
    exist.  Tries: as-is, under src/, under src/repro/, then any tree
    path whose tail matches (docs conventionally drop leading package
    directories: ``objectives/regression.py``)."""
    tok = tok.rstrip("/")
    for base in ("", "src", "src/repro"):
        cand = repo / base / tok
        if cand.exists():
            return cand
    name = tok.rsplit("/", 1)[-1]
    for p in repo.rglob(name):
        if ".git" in p.parts:
            continue
        if str(p).endswith("/" + tok) or p.name == tok:
            return p
    return None


def _check_pathlike(tok: str, repo: Path, problems: list[str],
                    where: str) -> None:
    path_part, _, symbol = tok.partition("::")
    if path_part.startswith(("~", "/")) or \
            _GENERATED.match(path_part.rstrip("/").rsplit("/", 1)[-1]):
        return
    target = _resolve_path(path_part, repo)
    if target is None:
        problems.append(f"{where}: path `{tok}` does not exist in tree")
        return
    if symbol and target.is_file():
        m = _SYMBOL.match(symbol)
        if m and m.group(0) not in target.read_text():
            problems.append(
                f"{where}: `{tok}` — symbol `{m.group(0)}` not found in "
                f"{target.relative_to(repo)}")


def _check_module(text: str, repo: Path, problems: list[str],
                  where: str) -> None:
    for m in _MODULE.finditer(text):
        mod = m.group(1)
        if mod in ("pip", "pytest", "venv", "http.server"):
            continue
        rel = mod.replace(".", "/")
        for base in ("", "src"):
            root = repo / base / rel
            if root.with_suffix(".py").exists() or \
                    (root / "__init__.py").exists():
                break
        else:
            problems.append(
                f"{where}: `python -m {mod}` — module not found in tree")


def _lint_inline(tok: str, repo: Path, flags: set[str],
                 problems: list[str], where: str) -> None:
    tok = tok.strip()
    if not tok or _is_placeholder(tok):
        return
    head, *rest = tok.split()
    if head.startswith("--"):
        flag = head.split("=")[0]
        if flag not in flags:
            problems.append(f"{where}: unknown CLI flag `{flag}`")
        return
    looks_pathy = ("/" in head and not head.startswith("-")) or \
        head.endswith(_PATH_EXTS) or head.split("::")[0].endswith(_PATH_EXTS)
    if looks_pathy:
        # skip obvious non-paths: spaces inside the "path", math, URLs
        if head.startswith(("http:", "https:")) or head in ("/",):
            return
        if not head.split("::")[0].endswith(_PATH_EXTS) \
                and not tok.endswith("/"):
            return  # names with a slash, like `kernels/aopt_gains`
        _check_pathlike(head if head.split("::")[0].endswith(_PATH_EXTS)
                        else tok, repo, problems, where)
        # trailing flags in the same token (`run.py --workload <cell>`)
        for piece in rest:
            if piece.startswith("--"):
                flag = piece.split("=")[0]
                if flag not in flags:
                    problems.append(
                        f"{where}: unknown CLI flag `{flag}` (in `{tok}`)")
    _check_module(tok, repo, problems, where)


def _lint_fenced(block: str, repo: Path, problems: list[str],
                 where: str) -> None:
    _check_module(block, repo, problems, where)
    for tok in re.findall(r"[\w./-]+\.py\b", block):
        if _is_placeholder(tok) or tok.startswith("-"):
            continue
        if _resolve_path(tok, repo) is None:
            problems.append(f"{where}: path `{tok}` does not exist in tree")


def lint_files(files, repo: Path = REPO) -> list[str]:
    flags = known_flags(repo)
    problems: list[str] = []
    for f in files:
        f = Path(f)
        in_fence = False
        fence_buf: list[str] = []
        fence_start = 0
        for i, line in enumerate(f.read_text().splitlines(), 1):
            if _FENCE.match(line.strip()):
                if in_fence:
                    _lint_fenced("\n".join(fence_buf), repo, problems,
                                 f"{f.name}:{fence_start}")
                    fence_buf = []
                else:
                    fence_start = i
                in_fence = not in_fence
                continue
            if in_fence:
                fence_buf.append(line)
                continue
            for m in _INLINE_CODE.finditer(line):
                _lint_inline(m.group(1), repo, flags, problems,
                             f"{f.name}:{i}")
    return problems


def main(argv) -> int:
    files = [Path(a) for a in argv[1:]]
    if not files:
        files = [REPO / "README.md"] + sorted((REPO / "docs").glob("*.md"))
    problems = lint_files(files)
    for p in problems:
        print(f"check_docs: {p}", file=sys.stderr)
    if problems:
        print(f"check_docs: {len(problems)} problem(s) in "
              f"{len(files)} file(s)", file=sys.stderr)
        return 1
    print(f"check_docs: {len(files)} file(s) clean")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
