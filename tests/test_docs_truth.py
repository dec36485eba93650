"""The documents a reader is sent to name only what the tree holds.

One case a document (README.md, BASELINE.md, the verify skill and every
docs/*.md outside docs/spec/): every repository file it names as
something to run or read exists, and every whole COMETBFT_TPU_* name it
mentions is a declared knob.  A deleted script, a renamed module or a
retired knob fails the case of each document that still sends a reader
there.  Stdlib only, nothing imported from the package but the knob
registry.
"""

from __future__ import annotations

import glob
import os
import re

import pytest

from cometbft_tpu.utils import envknobs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = sorted(
    ["README.md", "BASELINE.md", ".claude/skills/verify/SKILL.md"]
    + [
        os.path.relpath(p, REPO)
        for p in glob.glob(os.path.join(REPO, "docs", "*.md"))
    ]
)


# Top-level names .gitignore keeps out of a checkout are no part of the
# tree a document may send a reader to: a run leaves them behind.
with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as _f:
    _IGNORED = {ln.strip().rstrip("/") for ln in _f if ln.strip()}
TOP = {n for n in os.listdir(REPO) if n != ".git"} - _IGNORED
BASENAMES = set(TOP)
for _top in TOP:
    for _, _dirs, _names in os.walk(os.path.join(REPO, _top)):
        _dirs[:] = [d for d in _dirs if not d.startswith(".")]  # caches
        BASENAMES.update(_names)

_RUN_PATH = re.compile(r"\bpython3?\s+(?:-\w\s+)*([\w./-]+\.py)\b")
_RUN_MODULE = re.compile(r"\bpython3?\s+-m\s+(\w+(?:\.\w+)*)")
_QUOTED = re.compile(r"`([^`\s]+?\.(?:py|json|md))(?:::?[^`\s]*)?`")
_KNOB = re.compile(r"COMETBFT_TPU_[A-Z0-9_]*")
_PATTERN = set("*<>{}$")


def _missing_paths(text: str) -> list[str]:
    missing = []
    named = set(_RUN_PATH.findall(text)) | set(_QUOTED.findall(text))
    for path in sorted(named):
        if _PATTERN & set(path) or os.path.isabs(path) or path[0] == ".":
            continue  # a pattern, another machine's file, an extension
        head, _, rest = path.partition("/")
        if not rest:
            # a bare file name: some file of the tree carries it
            if path not in BASENAMES:
                missing.append(path)
        elif head in TOP and not os.path.exists(os.path.join(REPO, path)):
            missing.append(path)
    for module in sorted(set(_RUN_MODULE.findall(text))):
        head = module.split(".")[0]
        if head not in TOP:
            continue  # pytest, pip: not this repository's
        base = os.path.join(REPO, *module.split("."))
        if not (os.path.exists(base + ".py") or os.path.isdir(base)):
            missing.append(f"-m {module}")
    return missing


def _undeclared_knobs(text: str) -> list[str]:
    declared = {k.name for k in envknobs.all_knobs()}
    bad = set()
    for m in _KNOB.finditer(text):
        name, after = m.group(0), text[m.end():m.end() + 1]
        if name.endswith("_") or after in _PATTERN:
            continue  # written as a pattern, COMETBFT_TPU_HEALTH_*
        if name not in declared:
            bad.add(name)
    return sorted(bad)


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_what_the_tree_holds(doc):
    with open(os.path.join(REPO, doc), encoding="utf-8") as f:
        text = f.read()
    assert _missing_paths(text) == [], f"{doc} names files the tree lacks"
    assert _undeclared_knobs(text) == [], f"{doc} names undeclared knobs"


def test_the_check_sees_what_it_should():
    """The matcher itself: it finds a deleted script in each spelling a
    document uses, leaves patterns and other programs alone, and tells a
    retired knob from a declared one."""
    text = (
        "run `python gone_script.py`, or python3 scripts/gone.py --x 1;\n"
        "see `scripts/lint.py`, `scripts/gone_too.py:12`, `README.md`,\n"
        "`tests/test_wire.py::test_x`, `scripts/profile_*.py`,\n"
        "`out/soak.json`, python -m pytest tests/, python3 -m\n"
        "benchmarks.profile_rows, python -m cometbft_tpu.gone_module.\n"
        "COMETBFT_TPU_COMB_MIN, COMETBFT_TPU_NO_SUCH_KNOB,\n"
        "COMETBFT_TPU_HEALTH_*, COMETBFT_TPU_<NAME>\n"
    )
    assert _missing_paths(text) == [
        "gone_script.py", "scripts/gone.py", "scripts/gone_too.py",
        "-m cometbft_tpu.gone_module",
    ]
    assert _undeclared_knobs(text) == ["COMETBFT_TPU_NO_SUCH_KNOB"]
