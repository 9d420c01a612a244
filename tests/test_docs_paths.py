"""The documents name only files that exist.

One case per document (README.md and every docs/**/*.md): each backticked
path ending in .py, .json, .jsonl or .md must resolve in the tree — from the
repository's root, from the package (`scheduler/tick.py` is
`hyperqueue_tpu/scheduler/tick.py`) or from the document's own directory; a
bare file name may also be the one file of that name anywhere in the tree.
Paths into the reference implementation are upstream's and exempt, as are
the files a deployment or a user writes at run time.
"""

from __future__ import annotations

import os
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
DOCUMENTS = [Path("README.md")] + sorted(
    p.relative_to(REPO) for p in (REPO / "docs").rglob("*.md")
)
# a backticked token of path characters only: placeholders (`<dir>/x.json`,
# `~/.hq-tpu-server/NNN/access.json`) and globs are not paths into the tree
PATH_RE = re.compile(r"`([A-Za-z0-9_./-]+\.(?:py|jsonl|json|md))`")
UPSTREAM = re.compile(r"^(?:/root/reference/|crates/)|(?:^|/)experiment-[^/]*\.py$")
# written at run time under a server directory, or the user's own scripts
# in the examples: named in the documents, never part of the tree
NOT_IN_TREE = {
    "access.json", "client.json", "worker.json", "full.json",
    "federation.json", "lease.json", "train.py",
}


@pytest.fixture(scope="module")
def tree():
    """Every file of the checkout, but for what tools leave behind."""
    files = set()
    for root, dirs, names in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d not in ("__pycache__", "chiprun_out")]
        rel = Path(root).relative_to(REPO)
        files.update((rel / name).as_posix() for name in names)
    return files


def _resolves(token: str, document: Path, tree: set) -> bool:
    candidates = [
        Path(token), Path("hyperqueue_tpu") / token, document.parent / token,
    ]
    if any(c.as_posix() in tree for c in candidates):
        return True
    if "/" not in token:
        return sum(1 for p in tree if p.rsplit("/", 1)[-1] == token) == 1
    return False


@pytest.mark.parametrize("document", DOCUMENTS, ids=[str(d) for d in DOCUMENTS])
def test_document_names_only_files_that_exist(document, tree):
    text = (REPO / document).read_text()
    tokens = {
        t for t in PATH_RE.findall(text)
        if not UPSTREAM.search(t) and t not in NOT_IN_TREE
    }
    missing = sorted(t for t in tokens if not _resolves(t, document, tree))
    assert not missing, f"{document} names files that are not in the tree"
