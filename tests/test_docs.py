"""The documents name only what the tree has: every ``make <target>`` is a
target of the ``Makefile`` and every ``tools/<x>.py``, ``examples/<x>.py``
and root-level ``<x>.py`` a document names as code exists.  Code is an inline
backtick span or a line of a fenced block.  Paths of the reference
(``/root/reference/...``, ``autodist/...``) are not ours and are skipped."""
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = (["README.md"]
             + sorted(os.path.relpath(p, REPO) for p in
                      glob.glob(os.path.join(REPO, "docs", "*.md")))
             + [".claude/skills/verify/SKILL.md"])

_TARGET = re.compile(r"^([A-Za-z][\w-]*):", re.M)
_MAKE = re.compile(r"\bmake ([a-z][\w-]*)")
# ours: not the tail of a longer path (autodist/examples/..., /root/reference/...)
_IN_DIR = re.compile(r"(?<![\w/.-])((?:tools|examples)/[\w/-]+\.py)\b")
# a bare name is a command's script: the head of the span, or after `python`
_BARE = re.compile(r"(?:^|\bpython3? )([\w-]+\.py)\b")


def code_texts(text):
    """Inline backtick spans (they may wrap over a line end) and the lines
    of fenced blocks."""
    fenced = re.findall(r"^ *```[^\n]*\n(.*?)^ *```", text, re.M | re.S)
    prose = re.sub(r"^ *```[^\n]*\n.*?^ *```", "", text, flags=re.M | re.S)
    spans = [" ".join(s.split()) for s in re.findall(r"`([^`]+)`", prose)]
    return spans + [ln.strip() for block in fenced
                    for ln in block.splitlines()]


def module_basenames():
    """Documents shorten a module's path (``partitioner.py``): a bare name
    may be a file at the root, a tool, an example or a module of the package."""
    names = {f for d in ("", "tools", "examples")
             for f in os.listdir(os.path.join(REPO, d)) if f.endswith(".py")}
    for _dir, _sub, files in os.walk(os.path.join(REPO, "autodist_tpu")):
        names.update(f for f in files if f.endswith(".py"))
    return names


@pytest.mark.parametrize("doc", DOCUMENTS)
def test_document_names_only_what_the_tree_has(doc):
    with open(os.path.join(REPO, "Makefile")) as f:
        targets = set(_TARGET.findall(f.read()))
    with open(os.path.join(REPO, doc)) as f:
        spans = code_texts(f.read())
    known = module_basenames()
    missing = []
    for span in spans:
        missing += [f"make {t}" for t in _MAKE.findall(span)
                    if t not in targets]
        missing += [p for p in _IN_DIR.findall(span)
                    if not os.path.isfile(os.path.join(REPO, p))]
        missing += [n for n in _BARE.findall(span) if n not in known]
    assert not missing, f"{doc} names what the tree has not: {missing}"
