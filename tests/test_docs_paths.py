"""Every repo path a document names exists.

One case per document (``README.md`` and the files of ``docs/``): each
word inside back-quotes that reads as a path (``*.py``, ``*.md``,
``*.json``, ``*.jsonl``, ``*.toml``, with an optional ``:line`` or
``::test`` suffix) must be the tail of the path of some file in the tree
(``scenarios/http_driver.py`` is
``apex_tpu/serving/scenarios/http_driver.py``). So a deletion cannot leave
the documents behind.
"""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = ["README.md"] + sorted(
    f"docs/{n}" for n in os.listdir(os.path.join(REPO, "docs"))
    if n.endswith(".md"))

#: names the documents cite that are not this repo's files
NOT_OURS = {
    "handle.py", "_lamb.py",            # upstream apex's
    "tpu_lint_baseline.json",           # a user writes it
    "config.json",                      # a published model's
}

_SPAN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[\w./-]+\.(?:py|md|jsonl|json|toml)$")
_SUFFIX = re.compile(r"(?:::[\w\[\]-]+|:\d+(?:-\d+)?(?:,\d+(?:-\d+)?)*)+$")


@functools.cache
def _tree_files():
    out = []
    for folder, dirs, names in os.walk(REPO):
        # scratch and caches are dot- or underscore-directories, and the
        # chip tool's output directory
        dirs[:] = [d for d in dirs
                   if d[0] not in "._" and d != "chiprun_out"]
        rel = os.path.relpath(folder, REPO)
        for name in names:
            out.append("/" + os.path.normpath(
                os.path.join(rel, name)).replace(os.sep, "/"))
    return out


def cited_paths(text):
    for span in _SPAN.findall(text):
        for word in span.split():
            word = _SUFFIX.sub("", word.strip("()[],;\"'"))
            if word.startswith(("apex/", "/")) or word in NOT_OURS:
                continue            # upstream's tree; a URL path
            if _PATH.match(word):
                yield word


def test_the_rule_reads_what_it_should():
    text = ("see `serving/http.py:336-340`, `python tpu_aot.py --only x`, "
            "`tests/test_http.py::test_a[b-1]`, `apex/amp/handle.py`, "
            "`/metrics.json`, `AOT_<tag>.json`, `jax.numpy`, `out.json`")
    assert list(cited_paths(text)) == [
        "serving/http.py", "tpu_aot.py", "tests/test_http.py", "out.json"]


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_cited_path_exists(document):
    with open(os.path.join(REPO, document)) as f:
        cited = sorted(set(cited_paths(f.read())))
    missing = [p for p in cited
               if not any(f.endswith("/" + p.lstrip("./"))
                          for f in _tree_files())]
    assert not missing, f"{document} names files that do not exist: {missing}"
