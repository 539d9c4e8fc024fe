"""Docs layer (ISSUE 5 satellites): README/DESIGN exist, zero dangling
intra-repo links or DESIGN.md § citations, and the checker itself catches
rot (so the CI step is not a tautology)."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "tools"))

import check_links  # noqa: E402


def test_readme_and_design_exist():
    assert os.path.exists(os.path.join(ROOT, "README.md"))
    assert os.path.exists(os.path.join(ROOT, "docs", "DESIGN.md"))


def test_repo_has_no_dangling_links():
    errors = check_links.check(ROOT)
    assert not errors, "\n".join(errors)


def test_design_has_cited_sections():
    """Every section number cited anywhere must be a real ## N. heading —
    in particular the §4 serve/step.py cited while DESIGN.md didn't exist."""
    sections = check_links.design_sections(
        os.path.join(ROOT, "docs", "DESIGN.md"))
    assert sections is not None and {1, 2, 3, 4, 5} <= sections


def test_readme_covers_required_topics():
    with open(os.path.join(ROOT, "README.md")) as f:
        text = f.read()
    for required in ("Quickstart", "Repo map", "BENCHMARK.json",
                     "pytest", "examples/quickstart.py",
                     "`none`", "`ring`", "`bidir`", "`fused`"):
        assert required in text, f"README.md missing {required!r}"


@pytest.mark.parametrize("bad,msg", [
    ("see [x](no/such/file.md)", "no such file"),
    ("see [x](other.md#missing-anchor)", "dangling anchor"),
    # assembled at scan time so THIS file doesn't trip the repo-wide scan
    ("per DESIGN" + ".md §99", "sections"),
])
def test_checker_catches_rot(tmp_path, bad, msg):
    (tmp_path / "docs").mkdir()
    (tmp_path / "docs" / "DESIGN.md").write_text("## 1. Real section\n")
    (tmp_path / "other.md").write_text("## Present\n")
    (tmp_path / "doc.md").write_text(f"hello\n{bad}\n")
    errors = check_links.check(str(tmp_path))
    assert errors and any(msg in e for e in errors), (bad, errors)


def test_checker_requires_design_to_exist(tmp_path):
    (tmp_path / "mod.py").write_text("# cited in docs/DESIGN" + ".md §4\n")
    errors = check_links.check(str(tmp_path))
    assert errors and "does not exist" in errors[0]


def test_checker_ignores_code_fences_and_external(tmp_path):
    (tmp_path / "doc.md").write_text(
        "```\n[fake](not/a/file.md)\n```\n"
        "[ext](https://example.com/x) [mail](mailto:a@b.c)\n")
    assert check_links.check(str(tmp_path)) == []
