"""Lightweight docs link/path/symbol-rot checker (CI + tests/test_docs.py).

Scans the repo's documentation for references to repo files and fails when
one does not exist:

* markdown links ``[text](relative/path)`` (external http(s) and #anchors
  are skipped),
* inline-code path tokens like ``core/bcnn.py`` or ``docs/ARCHITECTURE.md``
  in both markdown files and the module docstrings of the listed Python
  files,
* inline-code **symbol** references like ``core/bcnn.py::forward_packed``
  or ``serve/slots.py::SlotScheduler.submit`` — the file must exist AND
  the named function/class/method/module-level constant must be defined in
  it (checked via ``ast``, so the paper→code cross-reference table in
  ``docs/ARCHITECTURE.md`` cannot silently rot when code is renamed).

A path token resolves if it exists relative to (a) the repo root, (b) the
directory of the file that mentions it, or (c) ``src/repro`` — so docs can
say ``serve/slots.py`` the way the code does. Trailing ``:line`` suffixes
on markdown links are stripped.

Usage:  python tools/check_links.py            # check the default doc set
        python tools/check_links.py A.md B.py  # check specific files
"""
from __future__ import annotations

import ast
import functools
import re
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent

# the default documentation surface kept rot-free in CI
DEFAULT_FILES = [
    "README.md",
    "docs/ARCHITECTURE.md",
    "docs/SERVING.md",
    "docs/PIPELINE.md",
    "docs/TRAINING.md",
    "src/repro/kernels/README.md",
    "src/repro/serve/slots.py",
    "src/repro/serve/engine.py",
    "src/repro/serve/bcnn_engine.py",
    "src/repro/serve/router.py",
    "src/repro/serve/replica.py",
    "src/repro/serve/autoscale.py",
    "tests/test_soak.py",
    "src/repro/parallel/pipeline.py",
    "src/repro/parallel/bcnn_pipeline.py",
    "src/repro/parallel/bcnn_data_parallel.py",
    "src/repro/kernels/xnor_conv_fused.py",
    "src/repro/core/bconv.py",
    "src/repro/train/bcnn_train.py",
    "src/repro/core/bcnn_artifact.py",
    "src/repro/launch/train_bcnn.py",
    "src/repro/models/xnor_lm.py",
    "src/repro/core/blinear.py",
    "src/repro/configs/xnor_lm_tiny.py",
    "src/repro/launch/serve.py",
    "tests/test_xnor_lm.py",
    "src/repro/core/execution_plan.py",
    "src/repro/kernels/autotune.py",
]

MD_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
# path-looking inline code: at least one '/' or a known doc/code suffix
CODE_PATH = re.compile(
    r"`([A-Za-z0-9_][A-Za-z0-9_./-]*\.(?:py|md|txt|ini|yml|json))`")
# `path/to/file.py::symbol` (optionally dotted: Class.method)
CODE_SYMBOL = re.compile(
    r"`([A-Za-z0-9_][A-Za-z0-9_./-]*\.py)::([A-Za-z_][A-Za-z0-9_]*"
    r"(?:\.[A-Za-z_][A-Za-z0-9_]*)?)`")
SEARCH_BASES = ("", "src/repro")


def _resolves(token: str, from_dir: Path) -> bool:
    token = token.split("#")[0]
    token = re.sub(r"(::.*|:\d+.*)$", "", token)
    if not token:
        return True
    return _resolve_path(token, from_dir) is not None


def _resolve_path(token: str, from_dir: Path) -> Path | None:
    cands = [from_dir / token] + [ROOT / b / token for b in SEARCH_BASES]
    for c in cands:
        if c.exists():
            return c
    return None


@functools.lru_cache(maxsize=None)
def _module_symbols(path: Path) -> set[str]:
    """Top-level names defined in a Python file: functions, classes,
    ``Class.method``s, and module-level assigned constants. Cached — the
    cross-reference table hits the same modules many times."""
    try:
        tree = ast.parse(path.read_text())
    except SyntaxError:
        return set()
    syms: set[str] = set()

    def targets(node):
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            tgts = node.targets if isinstance(node, ast.Assign) else [
                node.target]
            for t in tgts:
                if isinstance(t, ast.Name):
                    yield t.id

    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            # re-exports count: `from x import Y as Z` defines module.Z
            for alias in node.names:
                syms.add(alias.asname or alias.name.split(".")[0])
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            syms.add(node.name)
        elif isinstance(node, ast.ClassDef):
            syms.add(node.name)
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    syms.add(f"{node.name}.{sub.name}")
                for name in targets(sub):
                    syms.add(f"{node.name}.{name}")
        for name in targets(node):
            syms.add(name)
    return syms


def _symbol_resolves(file_token: str, symbol: str, from_dir: Path) -> bool:
    path = _resolve_path(file_token, from_dir)
    if path is None or path.suffix != ".py":
        return False
    return symbol in _module_symbols(path)


def _doc_text(path: Path) -> str:
    """The checkable text of a file: full content for markdown, the module
    docstring (plus top-level class/function docstrings) for Python."""
    text = path.read_text()
    if path.suffix != ".py":
        return text
    try:
        tree = ast.parse(text)
    except SyntaxError:
        return ""
    docs = [ast.get_docstring(tree) or ""]
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            docs.append(ast.get_docstring(node) or "")
    return "\n".join(docs)


def check_file(path: Path) -> list[str]:
    """Returns a list of human-readable problems found in ``path``."""
    problems = []
    text = _doc_text(path)
    try:
        rel = path.relative_to(ROOT)
    except ValueError:          # argv file outside the repo: report as-is
        rel = path
    refs = []
    if path.suffix == ".md":
        for m in MD_LINK.finditer(text):
            target = m.group(1)
            if target.startswith(("http://", "https://", "mailto:", "#")):
                continue
            refs.append(target)
    refs.extend(m.group(1) for m in CODE_PATH.finditer(text))
    for token in refs:
        if not _resolves(token, path.parent):
            problems.append(f"{rel}: broken reference `{token}`")
    for m in CODE_SYMBOL.finditer(text):
        file_token, symbol = m.group(1), m.group(2)
        if not _symbol_resolves(file_token, symbol, path.parent):
            problems.append(
                f"{rel}: broken symbol reference `{file_token}::{symbol}`")
    return problems


def main(argv: list[str]) -> int:
    files = [Path(a) for a in argv] if argv else [ROOT / f
                                                  for f in DEFAULT_FILES]
    problems = []
    for f in files:
        f = f.resolve()
        if not f.exists():
            problems.append(f"{f}: file does not exist")
            continue
        problems.extend(check_file(f))
    if problems:
        print("\n".join(problems))
        print(f"FAIL: {len(problems)} broken doc reference(s)")
        return 1
    print(f"ok: {len(files)} files, no broken references")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
