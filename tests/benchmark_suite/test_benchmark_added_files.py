"""A cell of an architecture the harness has never seen is ADDED: new files
under ``benchmark/`` and new entries in the manifest, and every file that was
there stays byte for byte what it was. What a later ``model_config`` PR may do
(add files and entries) is then enough for it.

The proof: a copy of ``benchmark/`` WITHOUT the rehearsal's Gemma-shaped
architecture (its four files and its configuration) cannot build the cell;
with exactly those paths added, and nothing else touched, the copy runs the
cell ``correct`` on the CPU, in a process that imports the copy's harness.
"""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmark import manifest as mf
from test_benchmark_rehearsal_cpu import check_line, rehearsal_manifest

CELL = "tiny-gemma.rehearsal-open"
# Relative to ``benchmark/``: what adding this architecture and its cell adds.
ADDED = ["architectures/rehearsal-gemma/program.py",
         "architectures/rehearsal-gemma/weights.py",
         "architectures/rehearsal-gemma/reference.py",
         "architectures/rehearsal-gemma/counts.py",
         "configs/rehearsal-tiny-gemma.json"]

RUN = """
import json, sys
from benchmark import manifest as mf
from benchmark.run import run_cell
assert mf.ROOT == sys.argv[1], (mf.ROOT, sys.argv[1])
with open(sys.argv[2]) as f:
    manifest = json.load(f)
line = run_cell(manifest, sys.argv[3], seed=2**31 + 29, seconds=2.0, trace=0,
                allow_cpu=True)
print(json.dumps(line))
"""


def digests(root: str) -> dict[str, str]:
    out = {}
    for folder, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def run_in(copy_root: str, manifest_path: str) -> subprocess.CompletedProcess:
    """The cell, by the copy's own harness: the copy comes first on the
    path, the repository behind it only for the program under test."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": os.pathsep.join([copy_root, mf.ROOT])}
    return subprocess.run(
        [sys.executable, "-c", RUN, copy_root, manifest_path, CELL],
        cwd=copy_root, env=env, capture_output=True, text=True, timeout=300)


def test_an_unseen_architecture_is_added_as_files_and_nothing_is_edited(
        tmp_path):
    here = mf.HERE
    copy_root = str(tmp_path / "checkout")
    copy = os.path.join(copy_root, "benchmark")
    shutil.copytree(here, copy, ignore=shutil.ignore_patterns("__pycache__"))
    for rel in ADDED:
        os.remove(os.path.join(copy, rel))
    os.rmdir(os.path.join(copy, "architectures", "rehearsal-gemma"))
    before = digests(copy)
    assert before and not set(before) & set(ADDED)

    manifest = rehearsal_manifest()
    manifest_path = str(tmp_path / "manifest.json")
    with open(manifest_path, "w") as f:
        json.dump(manifest, f)
    # Before the files are added the harness does not know the cell.
    p = run_in(copy_root, manifest_path)
    assert p.returncode != 0 and "rehearsal-tiny-gemma.json" in p.stderr

    for rel in ADDED:
        os.makedirs(os.path.dirname(os.path.join(copy, rel)), exist_ok=True)
        shutil.copyfile(os.path.join(here, rel), os.path.join(copy, rel))
    after = digests(copy)
    assert set(after) == set(before) | set(ADDED)
    assert {k: after[k] for k in before} == before, "an existing file changed"
    # ... and what was there is what the repository has, byte for byte.
    ours = digests(here)
    assert {k: ours[k] for k in before} == before

    p = run_in(copy_root, manifest_path)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    check_line(line, manifest, CELL, trace=False)
    assert digests(copy) == after, "a run wrote into the harness"
