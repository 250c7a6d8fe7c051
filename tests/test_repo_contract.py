"""What the repo says of itself holds: a document names files that
exist, the environment variables the program reads are the one table of
them in ``docs/``, the kernels' gate follows what it can observe, and
``scripts/chip_check.py``'s families name cells and tiny configurations
that exist. The source is read; no program runs but the gate's case."""

import ast
import functools
import glob
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = (["README.md", ".claude/skills/verify/SKILL.md"]
             + sorted(os.path.relpath(p, ROOT) for p in glob.glob(
                 os.path.join(ROOT, "docs", "*.md"))))
#: the directories a document's path is ours to check in (a path of
#: the reference starts with ``realhf/``)
OURS = ("realhf_tpu", "scripts", "benchmark", "tests")
SOURCE_DIRS = OURS + ("examples",)


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return f.read()


@functools.lru_cache(maxsize=None)
def _sources():
    """(path from the root, text) of every Python file of the tree."""
    paths = glob.glob(os.path.join(ROOT, "*.py"))
    for folder in SOURCE_DIRS:
        paths += glob.glob(os.path.join(ROOT, folder, "**", "*.py"),
                           recursive=True)
    return [(os.path.relpath(p, ROOT), open(p).read()) for p in paths]


# ----------------------------------------------------------------------
# (a) a document names what exists
# ----------------------------------------------------------------------
_PATH = re.compile(r"(?<![\w/.{-])((?:%s)/[\w./-]+)" % "|".join(OURS))
_ROOT_SCRIPT = re.compile(r"(?:python3?|chiprun --) +(\w+\.py)\b")
_MODULE = re.compile(r"python3? +-m +(realhf_tpu(?:\.\w+)+)")
_BARE = re.compile(r"`(\w+\.py)(?::[\w.]+)?`")
#: files of the reference that the documents name without a directory
REFERENCE_FILES = {"pipe_runner.py", "static_schedule.py", "controller.py",
                   "global_comm.py", "rw_paired_dataset.py"}


def _named_paths(text):
    for m in _PATH.finditer(text):
        if text[m.end():m.end() + 1] in ("*", "{", "<"):  # a pattern
            continue
        # `path.py:function`, `path.py::test`, a sentence's full stop
        path = m.group(1).split(":")[0].rstrip(".")
        if path.endswith("/") or "." in os.path.basename(path):
            yield path


@functools.lru_cache(maxsize=None)
def _file_names():
    names = set(os.listdir(ROOT))
    for folder in SOURCE_DIRS:
        for _, _, files in os.walk(os.path.join(ROOT, folder)):
            names |= set(files)
    return names


@pytest.mark.parametrize("document", DOCUMENTS)
def test_a_document_names_files_that_exist(document):
    text = _read(document)
    missing = {p for p in _named_paths(text)
               if not os.path.exists(os.path.join(ROOT, p))}
    missing |= {s for s in _ROOT_SCRIPT.findall(text)
                if not os.path.exists(os.path.join(ROOT, s))}
    # a file named without its directory is some file of the tree
    missing |= set(_BARE.findall(text)) - _file_names() - REFERENCE_FILES
    for module in _MODULE.findall(text):
        file = os.path.join(ROOT, *module.split("."))
        if not (os.path.exists(file + ".py")
                or os.path.exists(os.path.join(file, "__init__.py"))):
            missing.add(module)
    assert missing == set()


# ----------------------------------------------------------------------
# (b) the environment variables: one table, deployment settings only
# ----------------------------------------------------------------------
_VARIABLE = re.compile(r"REALHF_TPU_[A-Z0-9_]*[A-Z0-9]")
_TABLE_ROW = re.compile(r"^\| `(REALHF_TPU_[A-Z0-9_]+)` \|", re.M)


def test_the_variables_the_program_names_are_the_table_in_docs():
    named = set()
    for path, text in _sources():
        if path.startswith("realhf_tpu" + os.sep):
            named |= set(_VARIABLE.findall(text))
    tables = {d: _TABLE_ROW.findall(_read(d)) for d in DOCUMENTS}
    tables = {d: rows for d, rows in tables.items() if rows}
    assert list(tables) == ["docs/quickstart.md"]
    [rows] = tables.values()
    assert len(rows) == len(set(rows))
    assert set(rows) == named


def test_no_variable_of_the_old_kit_is_read():
    gone = "REALHF_" + "BENCH_"
    assert [path for path, text in _sources() if gone in text] == []


# ----------------------------------------------------------------------
# (c) the kernels' gate: the backend, or a trace under the interpreter
# ----------------------------------------------------------------------
@pytest.mark.parametrize("inside", [False, True],
                         ids=["outside", "under_the_interpreter"])
def test_the_kernels_gate_follows_the_interpreter(inside,
                                                  interpreted_kernels):
    import contextlib

    import jax

    from realhf_tpu.base.backend import pallas_enabled
    assert jax.default_backend() == "cpu"
    with (interpreted_kernels() if inside else contextlib.nullcontext()):
        assert pallas_enabled() is inside
    assert not pallas_enabled()


# ----------------------------------------------------------------------
# (d) chip_check.py's families, read without running anything
# ----------------------------------------------------------------------
def _value(node):
    """A literal of ``FAMILIES``: constants, tuples, ``{...}`` and
    ``dict(key=...)``."""
    if isinstance(node, ast.Dict):
        return {_value(k): _value(v) for k, v in zip(node.keys, node.values)}
    if isinstance(node, ast.Call) and ast.unparse(node.func) == "dict":
        return {k.arg: _value(k.value) for k in node.keywords}
    if isinstance(node, ast.Tuple):
        return tuple(_value(e) for e in node.elts)
    return ast.literal_eval(node)


def _families():
    for node in ast.parse(_read("scripts", "chip_check.py")).body:
        if (isinstance(node, ast.Assign)
                and ast.unparse(node.targets[0]) == "FAMILIES"):
            return _value(node.value)


FAMILIES = _families()


def test_no_case_list_is_empty():
    """The cases above are found, not listed: a glob or a parse that
    finds nothing would pass them all."""
    assert len(DOCUMENTS) >= 12 and len(FAMILIES) >= 4


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_family_of_chip_check_names_a_cell_and_a_tiny_configuration(
        family):
    spec = FAMILIES[family]
    cells = json.loads(_read("BENCHMARK.json"))
    assert spec["cell"] in [w["name"] for w in cells["workloads"]]
    assert os.path.exists(os.path.join(
        ROOT, "realhf_tpu", "models", "hf", family + ".py"))
    sub, name = spec["tiny"]
    tiny = json.loads(_read("tests", "benchmark", sub, "manifest.json"))
    [workload] = [w for w in tiny["workloads"] if w["name"] == name]
    [config] = [c for c in tiny["configs"] if c["name"] == workload["config"]]
    assert os.path.exists(os.path.join(ROOT, config["file"]))
    assert isinstance(spec["wrong_keys"], dict)
