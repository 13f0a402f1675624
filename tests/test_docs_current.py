"""The documents and the Makefile name only files that exist.

One case per document (``README.md``, ``CLAUDE.md``, ``PERF.md``, each
``docs/*.md``) and one per Makefile target whose recipe names files:
every repo-relative path in backticks, or in a recipe, exists.  A
document that explains a tool the tree no longer has fails here.
``ROADMAP.md`` and ``CHANGES.md`` are exempt: they record what was
removed.
"""

import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCS = ["README.md", "CLAUDE.md", "PERF.md"] + sorted(
    os.path.relpath(p, ROOT)
    for p in glob.glob(os.path.join(ROOT, "docs", "*.md")))

#: a path of this repo: under one of its directories, with an extension
_DIRS = r"(?:yask_tpu|tools|tests|docs|benchmark|examples)"
_PATH = re.compile(_DIRS + r"/[\w./*-]*\.(?:py|md|json|cpp|h)\b")
#: or a bare source or document name (``context.py``, ``PERF.md``)
_BARE = re.compile(r"(?<![\w/.*-])[A-Za-z_][\w-]*\.(?:py|md)\b(?!/)")
_TICKS = re.compile(r"`([^`\n]+)`")


def _basenames():
    names = set()
    for dp, dn, fn in os.walk(ROOT):
        dn[:] = [d for d in dn if not d.startswith(".")
                 and d not in ("__pycache__", "chiprun_out")]
        names.update(fn)
    return names


def missing_paths(text, basenames):
    """Paths named in ``text`` that the tree does not hold.  A name
    with a placeholder (``<seed>``, ``{name}``, ``...``) is skipped; a
    glob must match something; a bare name may live anywhere."""
    missing = []
    for m in _PATH.finditer(text):
        path = m.group(0)
        if not glob.glob(os.path.join(ROOT, path)):
            missing.append(path)
    for m in _BARE.finditer(_PATH.sub(" ", text)):
        if m.group(0) not in basenames:
            missing.append(m.group(0))
    return sorted(set(missing))


def _quoted(doc_text):
    return "\n".join(
        s for s in _TICKS.findall(doc_text)
        if not any(c in s for c in "<{") and "..." not in s)


def makefile_recipes():
    """target → its recipe text, for the targets that name files."""
    out, target = {}, None
    with open(os.path.join(ROOT, "Makefile")) as f:
        for line in f:
            m = re.match(r"^([A-Za-z][\w-]*):(?!=)", line)
            if m:
                target = m.group(1)
            elif line.startswith("\t") and target:
                out[target] = out.get(target, "") + line
            elif not line.strip():
                target = None
    return {t: r for t, r in out.items()
            if _PATH.search(r) or _BARE.search(r)}


RECIPES = makefile_recipes()


@pytest.fixture(scope="module")
def basenames():
    return _basenames()


@pytest.mark.parametrize("doc", DOCS)
def test_document_names_only_files_that_exist(doc, basenames):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    assert missing_paths(_quoted(text), basenames) == []


@pytest.mark.parametrize("target", sorted(RECIPES))
def test_makefile_target_names_only_files_that_exist(target, basenames):
    assert missing_paths(RECIPES[target], basenames) == []


def test_rule_sees_a_path_a_bare_name_a_glob_and_a_placeholder(basenames):
    text = _quoted(
        "`tools/no_such_tool.py` and `python no_such.py --x`, but not "
        "`tools/repo_lint.py:12`, `tools/serve*.py`, `context.py`, "
        "`tools/logs/conformance_<seed>.json` or `benchmark/run.py`; "
        "`docs/nothing*.md` matches nothing")
    assert missing_paths(text, basenames) == [
        "docs/nothing*.md", "no_such.py", "tools/no_such_tool.py"]
