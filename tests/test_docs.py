"""The documents name only files that exist: every back-quoted path under
one of this repo's directories, and every file a ``python <file>`` command
line runs. A deletion that leaves ``python some_tool.py`` in a quick start
fails here. History files (CHANGES.md, ROADMAP.md, PERF.md) are not held
to it; a document that quotes the reference tree's files says so in prose,
outside back quotes."""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.relpath(p, REPO)
    for p in glob.glob(os.path.join(REPO, "doc", "*.md")))
OUR_DIRS = ("tools/", "cxxnet_tpu/", "tests/", "example/", "benchmark/",
            "bin/", "onchip_logs/")

_QUOTED = re.compile(r"`([^`\n]+)`")
_PYTHON = re.compile(r"\bpython3?\s+((?:-[A-Za-z]\s+)*)([^\s`'\"|;&)]+)")


def _path_of(token: str) -> str:
    """The file part of a quoted reference: ``tests/test_x.py::test_y``,
    ``cxxnet_tpu/nnet/net.py:288-362``, ``tools/t.py --flag`` name the
    file before the mark."""
    token = token.split()[0]
    token = token.split("::")[0]
    token = re.sub(r":[0-9][0-9,\-]*$", "", token)
    return token.rstrip(".,;:)")


@functools.lru_cache(maxsize=None)
def _make_targets():
    """What ``make`` builds (bin/im2bin, ...): not in a fresh checkout,
    and rightly named by the documents."""
    with open(os.path.join(REPO, "Makefile"), encoding="utf-8") as f:
        return set(re.findall(r"^([A-Za-z0-9_./]+):", f.read(), re.M))


def _exists(path: str) -> bool:
    if any(c in path for c in "<>{}$"):
        return True            # a placeholder, not a name
    if path in _make_targets():
        return True
    full = os.path.join(REPO, path)
    if "*" in path or "?" in path:
        return bool(glob.glob(full))
    return os.path.exists(full)


def named_paths(text: str):
    """(kind, path) for every path the text names."""
    for m in _QUOTED.finditer(text):
        token = m.group(1).strip()
        if token.startswith(OUR_DIRS):
            yield "quoted", _path_of(token)
    for m in _PYTHON.finditer(text):
        flags, target = m.groups()
        if "-m" in flags.split() or "-c" in flags.split():
            continue
        if target.startswith("-") or not (
                target.endswith(".py") or target.startswith(OUR_DIRS)):
            continue
        yield "python", _path_of(target)


@pytest.mark.parametrize("doc", DOCS)
def test_paths_named_in_documents_exist(doc):
    text = open(os.path.join(REPO, doc), encoding="utf-8").read()
    missing = sorted({"%s %s" % (kind, path)
                      for kind, path in named_paths(text)
                      if not _exists(path)})
    assert not missing, "%s names files that are not there: %s" % (
        doc, ", ".join(missing))


def test_the_check_sees_a_missing_file():
    text = ("run `tools/no_such_tool.py --x`, then\n"
            "    python no_such_bench.py all\n"
            "and `tests/test_docs.py::test_x`, `bin/cxxnet`.")
    assert [(k, p) for k, p in named_paths(text) if not _exists(p)] == [
        ("quoted", "tools/no_such_tool.py"), ("python", "no_such_bench.py")]
