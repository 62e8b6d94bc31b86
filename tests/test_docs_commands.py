"""The documents name only what exists. For `README.md` and every
`docs/*.md`: each `python <path>.py` and `python -m <module>` written there
names a file or a module of this tree, and each `RAFIKI_*` variable written
there occurs in the program (`rafiki_tpu/`, `scripts/`, `benchmark/`,
`chip_smoke.py`). A name that ends in `_` is a prefix of such a variable."""

import functools
import importlib.util
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCS = ["README.md"] + sorted(
    os.path.join("docs", name)
    for name in os.listdir(os.path.join(REPO, "docs")) if name.endswith(".md"))
PROGRAM = ("rafiki_tpu", "scripts", "benchmark", "chip_smoke.py")
LEFT_BEHIND = {"__pycache__", "out"}  # what running leaves among the sources

COMMAND = re.compile(
    r"\bpython3?\s+(?:-[A-Za-z]\s+)*(?:-m\s+(?P<module>[\w.]+)"
    r"|(?P<path>[\w./-]+\.py)\b)")
VARIABLE = re.compile(r"(?<![A-Z0-9_])RAFIKI_[A-Z0-9_]+")


def read(path: str) -> str:
    with open(os.path.join(REPO, path), encoding="utf-8",
              errors="replace") as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def program_text() -> str:
    chunks = []
    for top in PROGRAM:
        if os.path.isfile(os.path.join(REPO, top)):
            chunks.append(read(top))
            continue
        for where, dirs, files in os.walk(os.path.join(REPO, top)):
            dirs[:] = [d for d in dirs if d not in LEFT_BEHIND]
            chunks.extend(read(os.path.join(where, name))
                          for name in sorted(files)
                          if not name.endswith((".pyc", ".so", ".pb", ".gz")))
    return "\n".join(chunks)


def module_exists(module: str) -> bool:
    try:
        return importlib.util.find_spec(module) is not None
    except (ImportError, ValueError):
        return False


@pytest.mark.parametrize("doc", DOCS)
def test_commands_name_files_and_modules_of_the_tree(doc):
    missing = []
    for m in COMMAND.finditer(read(doc)):
        if m.group("module"):
            if not module_exists(m.group("module")):
                missing.append("-m " + m.group("module"))
        elif not os.path.isfile(os.path.join(REPO, m.group("path"))):
            missing.append(m.group("path"))
    assert not missing, f"{doc} runs what is not in the tree: {missing}"


@pytest.mark.parametrize("doc", DOCS)
def test_variables_occur_in_the_program(doc):
    unknown = [
        name for name in sorted(set(VARIABLE.findall(read(doc))))
        if not re.search(re.escape(name)
                         + ("" if name.endswith("_") else r"(?![A-Z0-9_])"),
                         program_text())]
    assert not unknown, f"{doc} names variables the program has not: {unknown}"
