"""docs/ENV_VARS.md is generated from the env.py knob registry; keep
the committed file in lockstep with the code (regenerate with
``python -m mxnet_tpu.env > docs/ENV_VARS.md``). And every file a
document names is in the tree."""
import os
import re

import pytest

from mxnet_tpu import env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_env_vars_md_matches_registry():
    path = os.path.join(_REPO, "docs", "ENV_VARS.md")
    with open(path) as f:
        committed = f.read()
    assert committed == env.markdown_table(), (
        "docs/ENV_VARS.md is stale — regenerate with "
        "`python -m mxnet_tpu.env > docs/ENV_VARS.md`")


def test_fused_step_knobs_registered():
    for name in ("MXNET_FUSED_STEP", "MXNET_FUSED_STEP_CACHE_SIZE",
                 "MXNET_FUSED_STEP_DONATE"):
        assert name in env.KNOBS
        assert env.KNOBS[name][0] == "wired"


def test_markdown_table_covers_all_knobs():
    table = env.markdown_table()
    for name in env.KNOBS:
        assert f"`{name}`" in table


def test_readme_links_env_vars():
    with open(os.path.join(_REPO, "README.md")) as f:
        assert "docs/ENV_VARS.md" in f.read()


# ---------------------------------------------------------------------------
# a document cites only files that exist

_DOCS = ["README.md"] + sorted(
    "docs/" + n for n in os.listdir(os.path.join(_REPO, "docs"))
    if n.endswith(".md"))
_TOP_DIRS = ("mxnet_tpu/", "tests/", "tools/", "docs/", "benchmarks/",
             "native/", "examples/", "include/")
_FILE_EXTS = (".py", ".json", ".jsonl", ".md")
#: the reference implementation's own files, and names the program
#: writes at run time
_REFERENCE_ROOTS = ("python/mxnet/", "include/mxnet/")
_NOT_IN_THE_TREE = {"_op_translations.py", "manifest.json", "trace.json"}


@pytest.fixture(scope="module")
def tree():
    paths = set()
    for d, subs, names in os.walk(_REPO):
        subs[:] = [s for s in subs if not s.startswith(".")
                   and s not in ("__pycache__", "chiprun_out")]
        rel = os.path.relpath(d, _REPO)
        rel = "" if rel == "." else rel + "/"
        paths.update(rel + n for n in subs + names)
    return paths


def _expand_braces(tok):
    m = re.search(r"\{([^{}]*)\}", tok)
    if not m:
        return [tok]
    return [t for alt in m.group(1).split(",")
            for t in _expand_braces(tok[:m.start()] + alt + tok[m.end():])]


def _cited_paths(text):
    """Backticked tokens that name a file or directory of this
    repository: they start at a top-level directory or end in a source,
    record or document extension. ``path:line`` and ``path::test``
    suffixes are dropped, ``{a,b}`` alternatives expanded; URLs, HTTP
    routes and ``<placeholders>`` are not paths."""
    for tok in re.findall(r"`([^`\n]+)`", text):
        tok = re.sub(r"(::[\w\[\]-]+)+$", "", tok.strip())
        tok = re.sub(r":\d+(-\d+)?(,\d+(-\d+)?)*$", "", tok)
        if not re.fullmatch(r"[\w./{},-]+", tok) or tok.startswith("/") \
                or "//" in tok:
            continue
        for t in _expand_braces(tok):
            t = t.rstrip("/")
            if t.startswith(_REFERENCE_ROOTS) or t in _NOT_IN_THE_TREE:
                continue
            if t.startswith(_TOP_DIRS) or t.endswith(_FILE_EXTS):
                yield t


@pytest.mark.parametrize("doc", _DOCS)
def test_paths_named_in_docs_exist(doc, tree):
    """A path may be written from the root or from inside a package
    (``serving/state.py``), so any tracked path ending in it counts."""
    with open(os.path.join(_REPO, doc)) as f:
        cited = set(_cited_paths(f.read()))
    missing = sorted(t for t in cited if t not in tree
                     and not any(p.endswith("/" + t) for p in tree))
    assert not missing, f"{doc} names files that are not in the tree"
