"""The documents that tell a reader what to run name only what exists.

Every ``python[3] <path>.py`` a document shows is a file of the tree, every
``python[3] -m <module>`` a module that can be found, and the cells in
``README.md``'s "Measured performance" table are the ``workloads`` of
``BENCHMARK.json``, no more and no fewer.
"""

import importlib.util
import json
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent
DOCS = ["README.md", "examples/README.md", "MIGRATING.md",
        ".claude/skills/verify/SKILL.md"]

SCRIPT = re.compile(r"\bpython3?\s+([\w./-]+\.py)\b")
MODULE = re.compile(r"\bpython3?\s+-m\s+([\w.]+)")


@pytest.mark.parametrize("doc", DOCS)
def test_commands_a_document_shows_exist(doc):
    text = (ROOT / doc).read_text()
    scripts = sorted(set(SCRIPT.findall(text)))
    modules = sorted(set(MODULE.findall(text)))
    assert scripts or modules, f"{doc} shows no command: is the pattern stale?"
    gone = [s for s in scripts if not (ROOT / s).is_file()]
    gone += [m for m in modules if importlib.util.find_spec(m) is None]
    assert not gone, f"{doc} shows commands that do not exist: {gone}"


def test_readme_measured_performance_names_the_benchmarks_cells():
    text = (ROOT / "README.md").read_text()
    section = re.search(r"^## Measured performance\n(.*?)(?=^## |\Z)", text,
                        re.S | re.M)
    assert section, "README.md has no 'Measured performance' section"
    rows = re.findall(r"^\| *`([^`]+)` *\|", section.group(1), re.M)
    cells = [w["name"] for w in
             json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]
    assert sorted(rows) == sorted(cells)
