"""Documents point at files that exist.

A deleted script or module leaves its name behind in a recipe, and the
reader finds out by running it. For each document: every ``python
<file>`` command and every backticked path that starts with one of the
repository's directories must resolve in the tree, and a backticked bare
``name.py`` must be some file's name in it.
"""
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DIRS = ("scripts", "tests", "benchmarks", "configs", "docs",
        "pytorch_distributed_template_tpu")
# a file of the PyTorch template the documents compare against, which
# this tree has no twin of
REFERENCE_ONLY = {"parse_config.py"}
DOCUMENTS = sorted(
    [REPO / "README.md", REPO / "PARITY.md",
     REPO / ".github" / "workflows" / "tests.yml"]
    + list((REPO / "docs").glob("*.md"))
)

_COMMAND = re.compile(r"\bpython3?\s+(?:-[uXW]\s*\S*\s+)*([\w./-]+\.py)\b")
_BACKTICKED = re.compile(r"`([^`\s]+)`")
# what may follow a path inside backticks: `file.py:12`, `file.py::test`
_SUFFIX = re.compile(r"(:{1,2}[\w\[\]-].*|[.,;]+)$")


def _is_pattern(path: str) -> bool:
    return any(c in path for c in "*<>{}$[")


def _exists(path: str) -> bool:
    if (REPO / path).exists() or path in REFERENCE_ONLY:
        return True
    # a bare name: some file's name under one of the directories
    return "/" not in path and any(
        next((REPO / d).rglob(path), None) for d in DIRS)


def pointers(text: str) -> set:
    found = set(_COMMAND.findall(text))
    for token in _BACKTICKED.findall(text):
        path = _SUFFIX.sub("", token)
        first, slash, rest = path.partition("/")
        if (slash and rest and first in DIRS) or (
                not slash and path.endswith(".py")):
            found.add(path)
    return {p for p in found if not _is_pattern(p)}


def test_documents_found():
    assert len(DOCUMENTS) == 10


def test_pointer_rule_reads_commands_and_backticks():
    text = ("run `python scripts/a.py --x` then see `docs/B.md`, "
            "`serve.py:12`, `tests/test_c.py::test_d`, `configs/*.json`, "
            "`trainer.epochs` and\n    python -u train.py -c x\n")
    assert pointers(text) == {
        "scripts/a.py", "docs/B.md", "serve.py", "tests/test_c.py",
        "train.py"}


@pytest.mark.parametrize(
    "doc", DOCUMENTS, ids=[str(p.relative_to(REPO)) for p in DOCUMENTS])
def test_document_points_at_files_that_exist(doc):
    missing = sorted(p for p in pointers(doc.read_text())
                     if not _exists(p))
    assert not missing, f"{doc.relative_to(REPO)} names {missing}"
