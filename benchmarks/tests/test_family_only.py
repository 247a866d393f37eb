"""A later PR adds a configuration of another family with new files and
entries alone. In a scratch copy of the benchmark the `scratch` family
(`scratch_family/`) arrives as `families/scratch.py`, a reference of its own
beside it, a configuration, a limits file and entries in the copy's
`BENCHMARK.json`; its cell runs end to end (off the chip, at a tiny size), no
file that was there is edited, the family's own reference is what judges, and
`mfu.serve` counts with the family's own `matmul_params`."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest
from conftest import BENCH, HERE, ROOT

SCRATCH = os.path.join(HERE, "scratch_family")
CELL = "scratch-tiny.serve-chat"
LIKE = "galactica-1.3b.serve-chat"
SECONDS = 2
#: a kernel's roofline reader stays bound to its kernel's shape function, which
#: reads the `opt` configurations' keys: the scratch cell does not report it
NOT_REPORTED = ("flash_decode_roofline",)


def _hashes(folder):
    out = {}
    for base, dirs, files in os.walk(folder):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(base, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, folder)] = hashlib.sha256(f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    """The scratch copy with the family added: (its root, the hashes of the
    files that were there before, the names of the files added)."""
    root = tmp_path_factory.mktemp("family_only")
    bench = root / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "__pycache__", "*fixture.json"))
    before = _hashes(bench)
    # -- new files --------------------------------------------------------------
    shutil.copy(os.path.join(SCRATCH, "scratch.py"), bench / "families")
    with open(os.path.join(BENCH, "reference.py")) as f:
        mathematics = f.read()  # a copy of the text: nothing of it is imported
    (bench / "families" / "scratch_reference.py").write_text(mathematics)
    shutil.copy(os.path.join(SCRATCH, "scratch-tiny.json"), bench / "configs")
    shutil.copy(os.path.join(SCRATCH, CELL + ".json"), bench / "limits")
    # -- new entries ------------------------------------------------------------
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({
        "name": "scratch-tiny", "source": "benchmarks/tests/test_family_only.py",
        "file": "benchmarks/configs/scratch-tiny.json", "reduced": [],
        "why": "another family: its own weights, reference and counts"})
    manifest["workloads"].append({
        "name": CELL, "config": "scratch-tiny", "traffic": "serve-chat",
        "chips": 1, "why": "serve-chat's mix over the scratch family"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if LIKE in metric.get("workloads", []) and metric["name"] not in NOT_REPORTED:
            metric["workloads"].append(CELL)
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    added = set(_hashes(bench)) - set(before)
    return root, before, added


def _run(root, trace):
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         str(2**31 + 5), "--seconds", str(SECONDS), "--trace", str(trace),
         "--rehearsal", os.path.join(SCRATCH, "rehearsal.json")],
        cwd=root, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                 JAX_COMPILATION_CACHE_DIR=str(root / "cache")))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def traced(copy):
    return _run(copy[0], trace=1)


def test_the_cell_of_a_new_family_runs_and_is_correct(traced):
    assert traced["correct"] is True and traced["failed"] == 0
    assert traced["attempted"] > 0 and traced["metrics"] == {}
    assert traced["checks"]["tokens_compared"]["value"] >= 5


def test_no_file_that_was_there_is_edited(copy, traced):
    root, before, added = copy
    assert added == {
        os.path.join("families", "scratch.py"),
        os.path.join("families", "scratch_reference.py"),
        os.path.join("configs", "scratch-tiny.json"),
        os.path.join("limits", CELL + ".json")}
    after = _hashes(root / "benchmarks")
    assert {k: after[k] for k in before} == before


def test_mfu_serve_counts_with_the_familys_own_matmul_params(traced):
    """2 x matmul parameters x tokens over the window and the (stand-in) peak:
    the tokens this reading implies are a plausible count only under the
    scratch family's parameters, which are ten million times `opt`'s here."""
    with open(os.path.join(SCRATCH, "rehearsal.json")) as f:
        peak = json.load(f)["peaks"]["cpu"]["bf16_flops"]
    with open(os.path.join(SCRATCH, "scratch-tiny.json")) as f:
        counted = json.load(f)["counted_matmul_params"]
    share = traced["rehearsal_numbers"]["mfu.serve"]["value"]
    tokens = share / 100.0 * peak * SECONDS / (2.0 * counted)
    assert 1 <= tokens <= traced["attempted"] * 96


def test_the_familys_own_reference_is_what_judges(copy, traced):
    """The scratch reference made wrong on purpose, its last block left out:
    the same cell, the same seed, and `correct` comes out false."""
    root = copy[0]
    path = root / "benchmarks" / "families" / "scratch_reference.py"
    sound = path.read_text()
    whole = 'params["blocks"])'
    assert sound.count(whole) == 1
    path.write_text(sound.replace(
        whole, 'jax.tree_util.tree_map(lambda a: a[:-1], params["blocks"]))'))
    try:
        wrong = _run(root, trace=0)
    finally:
        path.write_text(sound)
    assert wrong["correct"] is False and wrong["failed"] == 0
    gap = wrong["checks"]["token_gap_max"]
    assert gap["value"] > 100 * gap["limit"]
    assert traced["checks"]["token_gap_max"]["value"] <= gap["limit"]
