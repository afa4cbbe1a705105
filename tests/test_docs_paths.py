"""The documents name files that exist. One case a document: every
back-quoted word that starts with a directory of the tree and ends in a
file extension (a trailing ``:line`` or ``::test`` allowed) is a file of
the checkout, and no document but PERF.md, whose section 6 records what
PRs 25 and 31 found and deleted, names a measurement path that is gone.
ROADMAP.md, CHANGES.md and SURVEY.md are history or about the reference
and are not read here."""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md", "PERF.md", ".claude/skills/verify/SKILL.md"] + \
    sorted(os.path.relpath(p, REPO)
           for p in glob.glob(os.path.join(REPO, "docs", "*.md")))

_PREFIXES = ("tools/", "tests/", "docs/", "benchmark/", "paddle_tpu/")
_GONE = ("bench.py", "VERDICT.md", "ADVICE.md", "MULTICHIP_r")
_PATH = re.compile(r"^(?P<path>[\w./-]+\.[A-Za-z0-9]+)"
                   r"(?:::.*|:[\d,\s–-]+)?$")


def _named_paths(text):
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.rstrip(",.;)")
            if not word.startswith(_PREFIXES) or set(word) & set("<*{"):
                continue
            m = _PATH.match(word)
            if m:
                yield m.group("path")


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_files_that_exist(document):
    with open(os.path.join(REPO, document), encoding="utf-8") as f:
        text = f.read()
    missing = sorted({p for p in _named_paths(text)
                      if not os.path.exists(os.path.join(REPO, p))})
    assert not missing, f"{document} names files that are not there"
    if document != "PERF.md":
        gone = [g for g in _GONE if g in text]
        assert not gone, f"{document} still names {gone}"
