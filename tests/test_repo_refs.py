"""What the repo's own build files and documents point at exists.

Every path a listed file names that starts with one of this repo's
top-level directories, every bare `*.py` / `*.json` file name, and every
`make <target>` must be found in the tree or the Makefile. Paths into the
reference implementation (`pkg/...`, `bpf/...`) are not this test's. A
document that still sends the reader to a deleted script or target fails
here, at the commit that deletes it.
"""
from __future__ import annotations

import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

FILES = [
    "Makefile", ".github/workflows/ci.yml", "README.md", "CLAUDE.md",
    "PARITY.md", "docs/architecture.md", "docs/tpu_sketch.md",
    "docs/observability.md", "docs/profiling.md", "docs/config.md",
    "scripts/demo.sh", "e2e/cluster/kind/Dockerfile",
]

#: this repo's top-level directories (the `benchmarks` directory went with
#: PR 30: a path under it is a stale reference by construction)
TOP_DIRS = ("netobserv_tpu", "tests", "scripts", "cellbench", "docs",
            "benchmarks")
#: what building and running leave behind: named in documents, ignored by git
GENERATED = re.compile(r"/build/|\.so$")
#: directories a walk for file names skips
SKIP_DIRS = {".git", "__pycache__", "chiprun_out", ".jax_cache",
             ".chip_scratch", ".cellbench_trace", ".cellbench_proof",
             ".pytest_cache", ".hypothesis"}

PATH_RE = re.compile(
    r"(?<![\w./-])((?:%s)/[\w./*{},<>-]*[\w*}>/])" % "|".join(TOP_DIRS))
BARE_RE = re.compile(r"(?<![\w./<>*-])([A-Za-z_][\w-]*\.(?:py|json))\b(?!/)")
MAKE_RE = re.compile(
    r"(?:`|&& |\| |^[ \t]*(?:\$ |run: |RUN )?)make[ \t]+(?:-s[ \t]+)?"
    r"([a-z][\w-]*)(?=`|[ \t]*$|[ \t]+#|[ \t]+>|[ \t]+&&|[ \t]+\|)",
    re.MULTILINE)


@functools.cache
def _known_names() -> frozenset[str]:
    """Base names of the tree's files, plus the `*.json` names the
    product's own sources mention: files it writes at run time
    (`FORMAT.json` in a checkpoint directory)."""
    names: set[str] = set()
    for at, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        names.update(files)
        if os.path.relpath(at, ROOT).startswith("netobserv_tpu"):
            for f in files:
                if f.endswith(".py"):
                    with open(os.path.join(at, f)) as fh:
                        names.update(n for n in BARE_RE.findall(fh.read())
                                     if n.endswith(".json"))
    return frozenset(names)


@functools.cache
def _make_targets() -> frozenset[str]:
    with open(os.path.join(ROOT, "Makefile")) as fh:
        return frozenset(
            re.findall(r"^([a-z][\w-]*):", fh.read(), re.MULTILINE))


def _path_exists(path: str) -> bool:
    if GENERATED.search(path) or "<" in path:
        return True  # a build product, or a placeholder like <cell>.json
    if "{" in path:  # tests/test_{a,b}.py: every alternative
        head, rest = path.split("{", 1)
        alts, tail = rest.split("}", 1)
        return all(_path_exists(head + a + tail) for a in alts.split(","))
    full = os.path.join(ROOT, path)
    if "*" in path:
        return bool(glob.glob(full))
    return os.path.exists(full)


@pytest.mark.parametrize("name", FILES)
def test_every_repo_path_and_make_target_named_exists(name):
    with open(os.path.join(ROOT, name)) as fh:
        text = fh.read()
    missing = sorted({p for p in PATH_RE.findall(text)
                      if not _path_exists(p)})
    # a bare file name must be SOME file of the tree (`state.py` is short
    # for its module; `BENCHMARK.json` is top-level), or one the product or
    # this very file writes (`> out.json`): a name no file has is a script
    # that went
    known = _known_names() | set(re.findall(r">\s*([\w.-]+)", text))
    missing += sorted({b for b in BARE_RE.findall(text) if b not in known})
    targets = _make_targets()
    missing += sorted({f"make {t}" for t in MAKE_RE.findall(text)
                       if t not in targets})
    assert not missing, f"{name} names what the tree does not have: {missing}"
