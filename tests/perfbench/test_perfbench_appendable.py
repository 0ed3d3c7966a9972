"""A later PR that adds a model appends: a configuration and its file, a cell
on it named last in every list that its sibling cell is in, and a per-layer
entry with its reader at the end of ``per_layer``. It edits no test. This
test does that in a copy of the benchmark, once for each cell taken as the
sibling, and runs the copy's own tests that read the manifest: none may hold
an entry by its place or a list as exactly its present cells.

The tests run are those that ``SELECT`` picks (``pytest -k``): the manifest's
own module, the entries looked up by name, each cell's configuration, mix
and list of metrics, and the pending entries. None of them rehearses a cell,
so the copy runs in well under a minute on the CPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import harness
from test_perfbench_rehearse import ROOT

SELECT = ("(manifest or catalogs_widths or reduced or program_config"
          " or issues_parameters or cell_reports or match_the_issue"
          " or roofline_reader or pending_entries)"
          " and not rehearse and not appendable")
ENTRY = "appended_reader"
READER = '''"""A per-layer metric that a later PR appends: it reads nothing."""


def read(obs):
    return None
'''
# run with the others, so that a copy whose tests read another tree's
# manifest fails
PROBE = '''import os

from perfbench import harness


def test_the_copy_manifest_holds_the_appended_cell():
    assert harness.ROOT == os.getcwd()
    assert {CELL!r} in [c["name"] for c in harness.load_manifest()["workloads"]]
'''
SIBLINGS = [c["name"] for c in harness.load_manifest()["workloads"]]


def append_cell(manifest, sibling):
    """Leave ``manifest`` as a PR that adds a copy of ``sibling``'s
    configuration leaves it; return the new cell's name, the copied
    configuration's file and the copy's."""
    cell = harness.find_cell(manifest, sibling)
    (entry,) = [c for c in manifest["configs"] if c["name"] == cell["config"]]
    name = cell["config"] + "-appended"
    new = dict(entry, name=name, file=f"perfbench/configs/{name}.json")
    manifest["configs"].append(new)
    new_cell = dict(cell, name=f"{name}.{cell['traffic']}", config=name)
    manifest["workloads"].append(new_cell)
    for section in ("end_to_end", "per_layer"):
        for m in manifest[section]:
            if sibling in m.get("workloads", ()):
                m["workloads"].append(new_cell["name"])
    reported = harness.cell_metrics(manifest, "per_layer", sibling)
    moves = next(m["name"] for m in harness.cell_metrics(
        manifest, "end_to_end", sibling) if m["name"] != "setup_s")
    manifest["per_layer"].append({
        "name": ENTRY, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": reported[0]["layer"],
        "moves": moves, "workloads": [new_cell["name"]]})
    return new_cell["name"], entry["file"], new["file"]


@pytest.mark.parametrize("sibling", SIBLINGS)
def test_a_cell_and_an_entry_append_without_editing_a_test(tmp_path,
                                                           sibling):
    root = tmp_path / "tree"
    for d in ("perfbench", os.path.join("tests", "perfbench")):
        shutil.copytree(os.path.join(ROOT, d), root / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    # the program under test is read where it is, not copied
    os.symlink(os.path.join(ROOT, "paddle_tpu"), root / "paddle_tpu")
    manifest = harness.load_manifest()
    cell, old, new = append_cell(manifest, sibling)
    (root / new).write_bytes((root / old).read_bytes())
    (root / "perfbench" / "layer_metrics" / f"{ENTRY}.py").write_text(READER)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest, indent=1))
    (root / "tests" / "perfbench" / "test_copy_manifest.py").write_text(
        PROBE.format(CELL=cell))
    (root / "pytest.ini").write_text("[pytest]\n")

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.pop("PYTEST_ADDOPTS", None)
    out = subprocess.run(
        [sys.executable, "-m", "pytest", "tests/perfbench", "-q",
         "-p", "no:cacheprovider", "-p", "no:randomly", "-k", SELECT],
        capture_output=True, text=True, timeout=60, cwd=root, env=env)
    assert out.returncode == 0, out.stdout[-6000:] + out.stderr[-2000:]
