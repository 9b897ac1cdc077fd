"""Every script a document tells its reader to run is in the tree: each
``python <file>.py`` inside a fenced block of the three documents that give
command lines, and every path of the repo that a ``run:`` step of the CI
workflow names, ``ruff check``'s arguments among them. Text is read, nothing
is run; no other test reads these files, and a deleted script stays in them
unnoticed."""

import os
import re

import pytest
import yaml

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ("README.md", "MIGRATION.md", ".claude/skills/verify/SKILL.md")
WORKFLOW = ".github/workflows/ci.yml"

FENCED = re.compile(r"^```[^\n]*\n(.*?)^```", re.M | re.S)
# python [-X ...] <file>.py: a script, not -m <module> and not -c <code>
SCRIPT = re.compile(r"\bpython3?\s+(?:-[A-Za-z]\s+\w+\s+)*([\w./-]+\.py)\b")
WORD = re.compile(r"[\w./-]+")


def _read(path):
    with open(os.path.join(ROOT, path), encoding="utf-8") as f:
        return f.read()


def documented_scripts():
    """(document, script) for each script named in a fenced block; a path
    outside the checkout (the multi-node drive's /tmp files) is no case."""
    cases = []
    for doc in DOCUMENTS:
        for block in FENCED.findall(_read(doc)):
            for script in SCRIPT.findall(block):
                if not os.path.isabs(script) and (doc, script) not in cases:
                    cases.append((doc, script))
    return cases


def repo_paths(command):
    """The words of a shell command that name something of this repo: a
    relative ``*.py``, a path whose first part is an entry of the root, or
    the module after ``python -m`` where it is this repo's package."""
    top = set(os.listdir(ROOT))
    found = []
    for word in WORD.findall(command):
        if os.path.isabs(word):
            continue
        word = word.rstrip("/.")
        first = word.split("/")[0]
        if first.startswith("mpi_operator_tpu."):
            found.append(first.replace(".", "/") + ".py")
        elif word.endswith(".py") or (first in top and "/" in word):
            found.append(word)
    return found


def workflow_paths():
    """(job/step, path) for each path of the repo that a ``run:`` step of
    the workflow names, ``ruff check``'s bare directory arguments among
    them."""
    jobs = yaml.safe_load(_read(WORKFLOW))["jobs"]
    cases = []
    for name, job in jobs.items():
        for i, step in enumerate(job["steps"]):
            command = step.get("run", "")
            paths = repo_paths(command)
            for line in command.splitlines():
                words = line.split()
                if words[:2] == ["ruff", "check"]:
                    paths += [w for w in words[2:]
                              if not w.startswith("-") and w not in paths]
            cases += [(f"{name}/{i}", path) for path in dict.fromkeys(paths)]
    return cases


SCRIPTS = documented_scripts()
PATHS = workflow_paths()


def _ids(cases):
    return [f"{where}:{what}" for where, what in cases]


@pytest.mark.parametrize("doc,script", SCRIPTS, ids=_ids(SCRIPTS))
def test_a_documented_script_exists(doc, script):
    assert os.path.isfile(os.path.join(ROOT, script)), (
        f"{doc} tells its reader to run {script}, which is not in the tree")


@pytest.mark.parametrize("step,path", PATHS, ids=_ids(PATHS))
def test_a_workflow_step_names_a_path_that_exists(step, path):
    assert os.path.exists(os.path.join(ROOT, path)), (
        f"{WORKFLOW} step {step} names {path}, which is not in the tree")


def test_the_extraction_sees_what_it_should():
    """The guards above are only as good as what they collect."""
    assert {("README.md", "chip_smoke.py"),
            ("README.md", "benchmark/run.py")} <= set(SCRIPTS)
    assert {"mpi_operator_tpu", "chip_smoke.py", "benchmark/run.py",
            "tests/test_native.py", "deploy/helm/tpu-operator",
            "mpi_operator_tpu/api/gen_schema.py"} <= {p for _, p in PATHS}
    assert repo_paths("python gone.py && python -m pytest tests_tpu/ -q "
                      "--ignore=tests/test_native.py") == [
        "gone.py", "tests/test_native.py"]
