"""Documentation integrity: the README / ARCHITECTURE / benchmark docs
exist, cross-link each other, and contain no rotted file references
(tools/check_links.py is the same checker CI runs as a standalone step)."""
import importlib.util
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def _load_checker():
    spec = importlib.util.spec_from_file_location(
        "check_links", ROOT / "tools" / "check_links.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_required_docs_exist():
    for f in ("README.md", "docs/ARCHITECTURE.md", "docs/SERVING.md",
              "docs/PIPELINE.md", "src/repro/kernels/README.md"):
        assert (ROOT / f).exists(), f"missing required doc: {f}"


def test_no_rotted_references():
    chk = _load_checker()
    problems = []
    for f in chk.DEFAULT_FILES:
        problems.extend(chk.check_file(ROOT / f))
    assert not problems, "\n".join(problems)


def test_readme_and_architecture_cross_link():
    readme = (ROOT / "README.md").read_text()
    arch = (ROOT / "docs/ARCHITECTURE.md").read_text()
    assert "docs/ARCHITECTURE.md" in readme
    assert "README.md" in arch
    # the operator guides are reachable from both entry docs
    for doc in ("docs/SERVING.md", "docs/PIPELINE.md"):
        assert doc in readme, f"README.md does not link {doc}"
        assert doc in arch, f"docs/ARCHITECTURE.md does not link {doc}"


def test_checker_catches_rot(tmp_path):
    chk = _load_checker()
    bad = ROOT / "README.md"          # must live under ROOT for relative_to
    good_problems = chk.check_file(bad)
    # synthesize a rotted doc and confirm the checker flags it
    rotted = ROOT / "docs" / "_rot_probe_test.md"
    rotted.write_text("see [gone](no/such/file.py) and `also/gone.md`\n")
    try:
        problems = chk.check_file(rotted)
    finally:
        rotted.unlink()
    assert len(problems) == 2 and all("broken" in p for p in problems)
    assert not good_problems


def test_checker_catches_symbol_rot(tmp_path):
    """`file.py::symbol` references are validated against the AST: a real
    symbol passes, a renamed/removed one (and a method) fails. (Path tokens
    resolve against the repo root, so the probe can live in tmp_path.)"""
    chk = _load_checker()
    rotted = tmp_path / "_rot_probe_symbols.md"
    rotted.write_text(
        "ok: `core/bcnn.py::forward_packed` and "
        "`serve/slots.py::SlotScheduler.submit` and "
        "`core/bitpack.py::PACK`\n"
        "rot: `core/bcnn.py::no_such_function` and "
        "`serve/slots.py::SlotScheduler.no_such_method`\n")
    problems = chk.check_file(rotted)
    assert len(problems) == 2, problems
    assert all("broken symbol" in p for p in problems)
    assert any("no_such_function" in p for p in problems)
    assert any("SlotScheduler.no_such_method" in p for p in problems)
