"""The documents name files, tests and records that exist.

A sentence that says which test holds an invariant, which tool to run or
which record bears a number out is only worth its words while the name
resolves. For each document below, every name written in backticks (or
in a fenced block) that is

- a path under `tests/`, `tools/`, `chipbench/`, `docs/` or `avenir_tpu/`,
- a `tests/<file>.py::<function>` (the function, too),
- a `*.py` without a directory (a script of the checkout's root, or a
  module the sentence names by its file alone), or
- a record of the root written in capitals (`PERF_LEDGER.jsonl`)

has to be there. Not held: names with a wildcard, a bracket or a
placeholder, files a command writes (`metrics.json`, `trace.json`), and
paths of the reference tree (`resource/...`, `src/main/...`).
"""

import functools
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ["README.md", "docs/DESIGN.md", "docs/graftlint.md",
             "docs/observability.md", "docs/tutorial_job_server.md",
             "docs/tutorial_scale_streaming.md",
             "docs/tutorial_freq_items_apriori.md"]
HELD_DIRS = ("tests/", "tools/", "chipbench/", "docs/", "avenir_tpu/")
#: where a `*.py` named without its directory may live
MODULE_DIRS = ("avenir_tpu", "tools", "tests", "chipbench")
_RECORD = re.compile(r"[A-Z][A-Z0-9_]*(_r\d+)?\.(json|jsonl|md|log)")
_BARE_PY = re.compile(r"[A-Za-z_]\w*\.py")
_NOT_A_NAME = re.compile(r"[*\[\]<>{}$]|\.\.\.")


def _quoted(text):
    """What the document writes as code: fenced blocks, then the inline
    spans of what is left."""
    fenced = re.findall(r"^```[^\n]*\n(.*?)^```", text, re.S | re.M)
    rest = re.sub(r"^```[^\n]*\n.*?^```", "", text, flags=re.S | re.M)
    return fenced + re.findall(r"`([^`\n]+)`", rest)


def _names(text):
    for chunk in _quoted(text):
        for token in chunk.split():
            token = token.strip("'\"(),;=").rstrip(".:")
            token = re.sub(r":\d+(-\d+)?$", "", token)     # file.py:120-130
            if token and not _NOT_A_NAME.search(token):
                yield token


def _defined(path, qualified):
    """Whether `Class::function` (or `function`) is defined in `path`."""
    with open(path) as fh:
        source = fh.read()
    return all(re.search(rf"^\s*(def|class) {re.escape(part)}\b", source, re.M)
               for part in qualified.split("::"))


@functools.lru_cache(maxsize=None)
def _module_files():
    found = set()
    for top in MODULE_DIRS:
        for _dir, _subdirs, files in os.walk(os.path.join(REPO, top)):
            found.update(f for f in files if f.endswith(".py"))
    return found


def missing_names(document):
    with open(os.path.join(REPO, document)) as fh:
        text = fh.read()
    modules = _module_files()
    missing = []
    for name in sorted(set(_names(text))):
        path, _, qualified = name.partition("::")
        if path.startswith(HELD_DIRS):
            full = os.path.join(REPO, path)
            if not os.path.exists(full):
                missing.append(name)
            elif qualified and not (os.path.isfile(full)
                                    and _defined(full, qualified)):
                missing.append(name)
        elif _BARE_PY.fullmatch(path):
            if not (os.path.isfile(os.path.join(REPO, path))
                    or path in modules):
                missing.append(name)
        elif _RECORD.fullmatch(path):
            if not os.path.isfile(os.path.join(REPO, path)):
                missing.append(name)
    return missing


@pytest.mark.parametrize("document", DOCUMENTS)
def test_document_names_only_what_exists(document):
    assert missing_names(document) == []


def test_the_check_finds_a_name_that_is_not_there(tmp_path):
    """The guard guards: a document that cites a script, a test function,
    a record and a module that do not exist is told so, and the names it
    is not held to pass."""
    doc = tmp_path / "doc.md"
    doc.write_text(
        "Run `python no_such_script.py --quick`, held by "
        "`tests/test_obs.py::test_no_such_test`; see `NO_SUCH_r05.json` "
        "and `avenir_tpu/no_such_module.py:12`.\n"
        "```\npython tools/no_such_tool.py --all\n```\n"
        "Fine: `tests/test_obs.py::test_chrome_export_schema`, "
        "`runner.py`, `PERF.md`, `tools/graftlint.py --all`, "
        "`RECORD_r0[2-5].json`, `metrics.json`, `resource/knn.sh`, "
        "`tests/test_graftlint*.py`, `<root>/out/<name>.json`.\n")
    found = missing_names(os.path.relpath(str(doc), REPO))
    assert found == ["NO_SUCH_r05.json", "avenir_tpu/no_such_module.py",
                     "no_such_script.py",
                     "tests/test_obs.py::test_no_such_test",
                     "tools/no_such_tool.py"]
