"""The documents name things that exist: every ``make <target>`` a document
gives is a target of the Makefile, and every backticked path into the repo
is a file or a directory of it.  One case per document."""

import functools
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCS = ["README.md", "PERF.md",
        *sorted(p.relative_to(REPO).as_posix()
                for p in (REPO / "docs").glob("*.md"))]

# A path may be written from the root, from the package or from the chip
# benchmark's directory (`engine/paged.py`, `harness/peaks.json`).
ROOTS = ("", "crowdllama_tpu/", "benchmarks/chip/")
# Directories whose files a bare basename (`gateway.py`) may stand for.
TREES = ("crowdllama_tpu", "benchmarks", "tests", "docs", "examples", "proto")
SUFFIXES = ("py", "json", "jsonl", "md", "toml", "proto", "cpp")
# Files a document may name that live outside the checkout: a Hugging Face
# checkpoint's config, and what a benchmark run leaves in its run directory.
OUTSIDE = {"config.json", "failure.json"}
NOT_LITERAL = set("*<>{}$…")


def _code(text: str) -> list[str]:
    """Inline code spans, and the lines of fenced blocks."""
    fenced = re.findall(r"```[^\n]*\n(.*?)```", text, re.S)
    inline = re.findall(r"`([^`\n]+)`", re.sub(r"```.*?```", "", text,
                                               flags=re.S))
    return inline + [ln for block in fenced for ln in block.splitlines()]


@functools.cache
def _basenames() -> frozenset[str]:
    names = {p.name for p in REPO.iterdir() if p.is_file()}
    for tree in TREES:
        names.update(p.name for p in (REPO / tree).rglob("*.*")
                     if "__pycache__" not in p.parts)
    return frozenset(names)


def _missing_path(token: str, basenames: frozenset[str]) -> bool:
    # `path:12`, `path:12-40`, `path::test_name`, `path:function`, `path:`
    token = re.sub(r":(:?[A-Za-z_][\w\[\]-]*|\d+(-\d+)?)?$", "", token)
    if "/" not in token:
        return (token.rsplit(".", 1)[-1] in SUFFIXES
                and re.fullmatch(r"[\w.-]+", token) is not None
                and token not in basenames and token not in OUTSIDE)
    # Up to the first component that is a pattern, not a name.
    literal = []
    for part in token.rstrip("/").split("/"):
        if NOT_LITERAL & set(part):
            break
        literal.append(part)
    if not literal:
        return False
    roots = [r for r in ROOTS if (REPO / r / literal[0]).is_dir()]
    return bool(roots) and not any(
        (REPO / r / "/".join(literal)).exists() for r in roots)


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_what_exists(doc):
    targets = set(re.findall(r"^([A-Za-z0-9_-]+):",
                             (REPO / "Makefile").read_text(), re.M))
    basenames = _basenames()
    wrong = []
    for span in _code((REPO / doc).read_text()):
        wrong += [f"make {t}" for t in
                  re.findall(r"\bmake ([a-z][a-z0-9-]*)", span)
                  if t not in targets]
        for token in span.split():
            token = token.strip("()[],;'\"")
            if token.startswith(("http", "/", "-", ".", "~")):
                continue
            if _missing_path(token, basenames):
                wrong.append(token)
    assert not wrong, f"{doc} names what is not in the repo: {sorted(set(wrong))}"
