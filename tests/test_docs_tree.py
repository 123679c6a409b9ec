"""The documents against the tree: every repo path, `PHANT_*` name and
`make` target that README.md or the verify skill names must still exist.

Text and `os.path` only. It does NOT ask that every knob the code reads be
documented (ROADMAP D14), only that a document sells nothing that is gone.
"""

import functools
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ("README.md", os.path.join(".claude", "skills", "verify", "SKILL.md"))
#: where a path a document writes short (`ops/root_engine.py`,
#: `engine.cc`) is looked for
PATH_BASES = ("", "phant_tpu", "native")
#: where a `PHANT_*` name has to be read
KNOB_DIRS = ("phant_tpu", "native", "scripts")
PATH_SUFFIXES = (
    ".py", ".md", ".json", ".jsonl", ".sh", ".cc", ".h", ".toml", "/",
)
#: made at run time, listed in .gitignore
RUNTIME_DIRS = ("build", "chiprun_out")


def _docs_text() -> str:
    text = []
    for doc in DOCS:
        with open(os.path.join(ROOT, doc), encoding="utf-8") as f:
            text.append(f.read())
    return "\n".join(text)


def _is_repo_path(word: str) -> bool:
    return (
        re.fullmatch(r"[\w-][\w.-]*(/[\w.-]+)*/?", word) is not None
        and word.endswith(PATH_SUFFIXES)
        and word.split("/")[0] not in RUNTIME_DIRS
    )


def _named() -> list:
    text = _docs_text()
    paths, targets = set(), set()
    for tick in re.findall(r"`([^`\n]+)`", text):
        words = tick.split()
        if words[0] == "make" and len(words) > 1:
            targets.add(words[1])
        for word in words:
            word = re.sub(r"(::\w+|:\d+(-\d+)?)$", "", word)
            paths.add(word[2:] if word.startswith("./") else word)
    # fenced blocks: `make soak   # comment` and `python scripts/x.py`
    for block in re.findall(r"```.*?\n(.*?)```", text, flags=re.S):
        targets.update(re.findall(r"^make ([a-z][\w-]*)", block, flags=re.M))
        paths.update(re.findall(r"(?<![\w./-])[\w.-]+(?:/[\w.-]+)+", block))
    knobs = set(re.findall(r"\bPHANT_[A-Z0-9]+(?:_[A-Z0-9]+)*\b", text))
    return (
        [("path", p) for p in sorted(filter(_is_repo_path, paths))]
        + [("knob", k) for k in sorted(knobs)]
        + [("make", t) for t in sorted(targets)]
    )


@functools.lru_cache(maxsize=None)
def _sources() -> str:
    chunks = []
    for top in KNOB_DIRS:
        for here, dirs, files in os.walk(os.path.join(ROOT, top)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith((".py", ".cc", ".h", ".sh")):
                    with open(os.path.join(here, name), encoding="utf-8") as f:
                        chunks.append(f.read())
    return "\n".join(chunks)


@functools.lru_cache(maxsize=None)
def _makefile() -> str:
    with open(os.path.join(ROOT, "Makefile"), encoding="utf-8") as f:
        return f.read()


@pytest.mark.parametrize("kind,name", _named(), ids=str)
def test_documents_name_what_the_tree_has(kind, name):
    if kind == "path":
        assert any(
            os.path.exists(os.path.join(ROOT, base, name)) for base in PATH_BASES
        ), f"a document names `{name}`, which is not in the tree"
    elif kind == "knob":
        assert re.search(rf"\b{name}\b", _sources()), (
            f"a document names {name}; nothing under {KNOB_DIRS} reads it"
        )
    else:
        assert re.search(rf"^{re.escape(name)}:", _makefile(), flags=re.M), (
            f"a document names `make {name}`; the Makefile has no such target"
        )
