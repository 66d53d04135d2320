"""The documentation front door stays honest.

README/docs relative links must resolve (tools/docs_lint.py — also a CI
step) and the docs must actually mention the engine modes they promise to
explain.
"""
import subprocess
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent


def test_docs_links_resolve():
    res = subprocess.run(
        [sys.executable, str(ROOT / "tools" / "docs_lint.py")],
        capture_output=True, text=True, timeout=60,
    )
    assert res.returncode == 0, res.stdout + res.stderr


def test_readme_covers_engines_and_verify():
    text = (ROOT / "README.md").read_text()
    for needle in (
        "superstep", "fused", "batched", "reference",  # the four engine modes
        "examples/quickstart.py",
        "python -m pytest -x -q",  # tier-1 verify command
        "EXPERIMENTS.md", "ROADMAP.md", "docs/architecture.md",
    ):
        assert needle in text, f"README.md must mention {needle!r}"


def test_architecture_documents_contract_and_layout():
    text = (ROOT / "docs" / "architecture.md").read_text()
    for needle in ("mermaid", "(C, E_max, D)", "superstep", "WireCodec",
                   "bitwise"):
        assert needle in text, f"docs/architecture.md must mention {needle!r}"


def test_docs_lint_names_an_undocumented_scope(tmp_path, monkeypatch):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "docs_lint", ROOT / "tools" / "docs_lint.py")
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    src = tmp_path / "src" / "repro" / "core"
    src.mkdir(parents=True)
    (src / "telemetry.py").write_text(
        'SCOPES = (\n    "feds/train", "feds/train/gather",\n)\n')
    (tmp_path / "PERF.md").write_text("| `feds/train` | ... |\n")
    monkeypatch.setattr(lint, "ROOT", tmp_path)
    assert lint.undocumented_scopes() == [
        "PERF.md: named scope `feds/train/gather` is in the program but "
        "not documented"]
    (tmp_path / "PERF.md").write_text("`feds/train` and `feds/train/gather`")
    assert lint.undocumented_scopes() == []
