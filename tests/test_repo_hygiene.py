"""The repo keeps its own promises: every registered component is exercised,
every package is on the architecture page, every relative docs link
resolves.

Each check is parametrised from what it lists at collection time — the
registries, the packages under ``src/repro``, the markdown files — so a new
component, package or page is checked with no edit here.
"""
import re
from pathlib import Path

import pytest

from repro.api.registry import populate

ROOT = Path(__file__).resolve().parent.parent
ARCHITECTURE = ROOT / "docs" / "architecture.md"


# ------------------------------------------------------- registry coverage

def _registered_names():
    return [(reg, name) for reg, registry in populate().items()
            for name in registry.names()]


@pytest.fixture(scope="module")
def exercising_sources() -> str:
    """The test and benchmark sources, this file left out so that it cannot
    satisfy its own check."""
    files = [p for d in ("tests", "benchmarks")
             for p in sorted((ROOT / d).glob("*.py"))
             if p.resolve() != Path(__file__).resolve()]
    return "\n".join(p.read_text() for p in files)


@pytest.mark.parametrize("registry,name", _registered_names())
def test_registered_name_is_exercised(registry, name, exercising_sources):
    """A quoted literal, not a bare substring: ``mean`` alone would be
    satisfied by ``np.nanmean`` or ``mean_model``."""
    quoted = rf"""['"]{re.escape(name)}['"]"""
    assert re.search(quoted, exercising_sources), (
        f"registry {registry!r}: component {name!r} is quoted by no test "
        f"or benchmark scenario in tests/ or benchmarks/")


# ------------------------------------------------------------------- docs

def _packages():
    return sorted(p.name for p in (ROOT / "src" / "repro").iterdir()
                  if (p / "__init__.py").is_file())


@pytest.mark.parametrize("package", _packages())
def test_package_is_named_in_architecture(package):
    assert re.search(rf"\brepro/{re.escape(package)}\b",
                     ARCHITECTURE.read_text()), (
        f"docs/architecture.md does not name repro/{package}")


_LINK = re.compile(r"\[[^\]]*\]\(([^)#\s]+)(?:#[^)]*)?\)")


def _markdown_files():
    return ["README.md"] + sorted(
        p.relative_to(ROOT).as_posix()
        for p in (ROOT / "docs").glob("*.md"))


@pytest.mark.parametrize("doc", _markdown_files())
def test_relative_links_resolve(doc):
    path = ROOT / doc
    broken = [target for target in _LINK.findall(path.read_text())
              if not target.startswith(("http://", "https://", "mailto:"))
              and not (path.parent / target).exists()]
    assert not broken, f"{doc}: broken relative links {broken}"
