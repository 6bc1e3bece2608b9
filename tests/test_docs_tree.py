"""Doc-drift gate beside test_metrics_doc.py: the Makefile runs only what
the tree has, and README.md, the Makefile and the verify skill name no
file that is gone."""

import importlib.util
import os
import re

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "Makefile", os.path.join(".claude", "skills", "verify",
                                              "SKILL.md")]
# names a run writes or an example invents, not files of the tree
NOT_OF_THE_TREE = {"quokka_trace.json", "trace.json", "serve_my_queries.py"}
# a path-like token ending in an extension the repo commits; not part of a
# longer path, a glob or a <placeholder>
_FILE = re.compile(r"(?<![\w/<>*$.-])((?:[\w.-]+/)*[\w-]+"
                   r"\.(?:py|json|jsonl|md|cc|cpp|h|toml|txt|yaml))\b")


def _read(rel):
    with open(os.path.join(_ROOT, rel), encoding="utf-8") as f:
        return f.read()


def _tree():
    skip = {".git", ".jax_cache", "chiprun_out", "__pycache__", ".cache"}
    files = set()
    for dirpath, dirnames, filenames in os.walk(_ROOT):
        dirnames[:] = [d for d in dirnames if d not in skip]
        files.update(os.path.relpath(os.path.join(dirpath, f), _ROOT)
                     for f in filenames)
    return files


def test_makefile_recipes_run_what_exists():
    recipes = [line for line in _read("Makefile").splitlines()
               if line.startswith("\t")]
    modules = set(re.findall(r"-m\s+(quokka_tpu[\w.]*)", "\n".join(recipes)))
    scripts = set(re.findall(r"\$\(PY\)\s+([\w./-]+\.py)",
                             "\n".join(recipes)))
    assert len(modules) > 10 and scripts
    gone = sorted(m for m in modules if importlib.util.find_spec(m) is None)
    gone += sorted(s for s in scripts
                   if not os.path.exists(os.path.join(_ROOT, s)))
    assert not gone, f"the Makefile runs what the tree does not have: {gone}"


@pytest.mark.parametrize("doc", DOCS)
def test_doc_names_no_file_that_is_gone(doc):
    """A named file is found as written or under some directory of the tree
    (``obs/spans.py`` for ``quokka_tpu/obs/spans.py``)."""
    tree = _tree()
    named = set(_FILE.findall(_read(doc))) - NOT_OF_THE_TREE
    assert named, f"{doc}: the pattern finds no file name at all"
    gone = sorted(n for n in named if n not in tree
                  and not any(t.endswith(os.sep + n) for t in tree))
    assert not gone, f"{doc} names files the tree does not have: {gone}"
