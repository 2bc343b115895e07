"""The documents a new owner reads first name files that exist: every
repo-relative `*.py` / `*.json` / `*.md` path that README.md,
COMPONENTS.md or the verify skill gives in backticks is in the tree.
A path with a directory is looked up from the root or from `ray_tpu/`
(the documents name modules without the package prefix); a bare file
name must be a file at the root or the name of a module somewhere in
the tree."""

import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md", "COMPONENTS.md", ".claude/skills/verify/SKILL.md"]

# a path made of plain name characters, ending in one of the suffixes;
# placeholders (<name>, *, {a,b}, …) and URLs do not match
_PATH = re.compile(r"^(?:\./)?[A-Za-z0-9_.\-]+(?:/[A-Za-z0-9_.\-]+)*"
                   r"\.(?:py|json|md)$")
# the reference's own tree (SURVEY.md cites it by these prefixes)
_REFERENCE = ("python/ray/", "src/ray/")
# what building, running or a user's own command writes: not in the tree
_MADE_AT_RUN_TIME = {
    "timeline.json", "trace.json", "result.json",
    "perfetto_trace.json.gz",
}


def _named_paths(text):
    for span in re.findall(r"`([^`\n]+)`", text):
        for word in span.split():
            word = word.strip("()[],;:'\"")
            if _PATH.match(word) and not word.startswith(("/", "~")):
                yield word


def _basenames():
    names = set(os.listdir(ROOT))
    for top in ("ray_tpu", "tests", "benchmarks", "scripts"):
        for _, _, files in os.walk(os.path.join(ROOT, top)):
            names.update(files)
    return names


def _exists(path, basenames):
    if "/" not in path:
        return path in basenames
    return any(os.path.exists(os.path.join(ROOT, pre, path))
               for pre in ("", "ray_tpu"))


@pytest.mark.parametrize("doc", DOCS)
def test_named_paths_exist(doc):
    with open(os.path.join(ROOT, doc)) as f:
        text = f.read()
    named = sorted(p for p in set(_named_paths(text)) - _MADE_AT_RUN_TIME
                   if not p.startswith(_REFERENCE))
    assert named, f"{doc} names no path: the pattern no longer reads it"
    basenames = _basenames()
    missing = [p for p in named if not _exists(p, basenames)]
    assert not missing, f"{doc} names files that are not in the tree: {missing}"
