"""The documents stay navigable: README/DESIGN links resolve, and every
``DESIGN.md §N`` citation in the docs, sources, tests and examples names a
top-level DESIGN.md heading (the section numbering is load-bearing)."""

import re
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def test_readme_and_design_relative_links_resolve():
    broken = []
    for name in ("README.md", "DESIGN.md"):
        for target in re.findall(r"\]\(([^)\s]+)\)", (ROOT / name).read_text()):
            target = target.split("#")[0]
            if not target or "://" in target or target.startswith("mailto:"):
                continue
            if not (ROOT / target).exists():
                broken.append(f"{name} -> {target}")
    assert not broken, broken


def test_design_section_citations_resolve():
    sections = set(
        re.findall(r"^## (§\d+)", (ROOT / "DESIGN.md").read_text(), flags=re.M)
    )
    paths = [ROOT / n for n in ("README.md", "DESIGN.md", "ROADMAP.md")]
    for top in ("src", "tests", "examples"):
        paths += sorted((ROOT / top).rglob("*.py"))
    missing = []
    for path in paths:
        # a compound citation ("DESIGN.md §4/§6") cites every listed section
        text = path.read_text()
        for group in re.findall(r"DESIGN\.md ((?:§\d+[/,])*§\d+)", text):
            missing += [
                f"{path.relative_to(ROOT)}: {ref}"
                for ref in re.findall(r"§\d+", group)
                if ref not in sections
            ]
    assert sections and not missing, missing
