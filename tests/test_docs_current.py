"""The documents a newcomer reads first name only what the tree holds.

One case a document: every ``python -m dlrover_tpu.x.y`` names a module
of the tree, every ``python <file>.py`` a file, and every backticked
path under one of the tree's directories, or a root file's bare name,
something that exists.  ``CHANGES.md``, ``ROADMAP.md`` and ``PERF.md``
are histories and are not read.  Where a case fails, the document is
wrong: repair the document.
"""

import fnmatch
import functools
import glob
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

DOCUMENTS = (
    ["README.md"]
    + sorted(
        os.path.relpath(p, ROOT)
        for p in glob.glob(os.path.join(ROOT, "docs", "*.md"))
    )
    + ["scripts/ci_check.sh", ".claude/skills/verify/SKILL.md"]
)

#: a backticked path is held to the tree when it starts with one of these
DIRECTORIES = (
    "dlrover_tpu/", "benchmarks/", "tests/", "tests_tpu/", "scripts/",
    "examples/", "docs/", "native/",
)
#: ...or is a bare name with one of these endings: a root file, or the
#: basename by which a document calls a module it has just placed
ROOT_ENDINGS = (".py", ".json", ".jsonl", ".md")

#: bare names of files a run writes or an example invents: not the tree's
WRITTEN_AT_RUN_TIME = {
    "INCIDENT.json",  # the incident engine's verdict, under the job's dir
    "meta.json",  # a sealed checkpoint's manifest entry
    "a.py",  # docs/graftlint.md's example of a linted file
}

_TOKEN = re.compile(r"`([^`\n]+)`")
_PATH = re.compile(r"^[A-Za-z0-9_.\-/]+$")
_MODULE_RUN = re.compile(r"python3?\s+(?:-X\s+\S+\s+)?-m\s+(dlrover_tpu[\w.]*)")
_FILE_RUN = re.compile(r"python3?\s+([A-Za-z0-9_.\-/]+\.py)\b")


@functools.cache
def _ignored_patterns():
    with open(os.path.join(ROOT, ".gitignore")) as f:
        return [
            line.strip() for line in f
            if line.strip() and not line.startswith("#")
        ]


def _git_ignores(path, patterns):
    """Whether the root ``.gitignore`` covers ``path`` (as much of the
    format as that file uses): building and running leave such files
    behind, and a checkout need not hold them."""
    parts = path.rstrip("/").split("/")
    for pattern in patterns:
        if pattern.endswith("/"):
            directory = pattern.strip("/").split("/")
            for i in range(len(parts) - len(directory) + 1):
                if parts[i : i + len(directory)] == directory:
                    return True
        elif pattern.startswith("/"):
            if fnmatch.fnmatch(parts[0], pattern[1:]):
                return True
        elif any(fnmatch.fnmatch(part, pattern) for part in parts):
            return True
    return False


@functools.cache
def _basenames():
    """Basenames of the tree's files (the directories above, and the
    root), for a document that writes ``snapshot.py`` after placing it."""
    names = set(os.listdir(ROOT))
    for directory in DIRECTORIES:
        for _, _, files in os.walk(os.path.join(ROOT, directory)):
            names.update(files)
    return names


def _module_exists(module):
    base = os.path.join(ROOT, *module.split("."))
    return os.path.isfile(base + ".py") or os.path.isfile(
        os.path.join(base, "__init__.py")
    )


def _claims(text):
    """(kind, what) for everything in ``text`` that names a module or a
    file of the tree."""
    for module in _MODULE_RUN.findall(text):
        yield "module", module.rstrip(".")
    for path in _FILE_RUN.findall(text):
        if not path.startswith("/"):
            yield "file", path
    for token in _TOKEN.findall(text):
        words = token.split()
        for word in words:
            # `path:123`, `path::Test::test`: the path alone
            path = word.split(":", 1)[0].rstrip(".,")
            if not _PATH.match(path):
                continue  # globs, placeholders, prose
            if path.startswith(DIRECTORIES):
                yield "path", path
            elif len(words) == 1 and "/" not in path and path.endswith(
                ROOT_ENDINGS
            ):
                yield "name", path  # inside a command it may be an output


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    with open(os.path.join(ROOT, document)) as f:
        text = f.read()
    patterns, basenames = _ignored_patterns(), _basenames()
    wrong = set()
    for kind, what in _claims(text):
        if kind == "module":
            ok = _module_exists(what)
        elif kind == "name":
            ok = (
                what in basenames or what in WRITTEN_AT_RUN_TIME
                or _git_ignores(what, patterns)
            )
        else:
            ok = os.path.exists(os.path.join(ROOT, what)) or _git_ignores(
                what, patterns
            )
        if not ok:
            wrong.add(f"{kind} {what}")
    assert not wrong, (
        f"{document} names what the tree does not hold: {sorted(wrong)}"
    )


def test_the_scan_reads_claims():
    """A scan that finds nothing would pass every document."""
    claims = set(_claims(
        "run `python -m dlrover_tpu.analysis --timing dlrover_tpu/`, then\n"
        "python3 benchmarks/run.py; see `tests/test_x.py::TestY::test_z`,\n"
        "`PERF.md`, `docs/*.md` and `dlrover_tpu/trainer/train.py:12`."
    ))
    assert claims == {
        ("module", "dlrover_tpu.analysis"), ("file", "benchmarks/run.py"),
        ("path", "dlrover_tpu/"), ("path", "tests/test_x.py"),
        ("name", "PERF.md"), ("path", "dlrover_tpu/trainer/train.py"),
    }
