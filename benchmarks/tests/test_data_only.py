"""A later PR adds a cell with data files and a `workloads` entry alone: in a
scratch copy of the benchmark, `serve-chat`'s lengths offered as a backlog run
end to end (off the chip, at the rehearsal's tiny size) with no file that is
there edited."""
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH, HERE, ROOT


def test_a_cell_arrives_as_data(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "*fixture.json"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = "galactica-1.3b.serve-backlog"
    manifest["workloads"].append({
        "name": cell, "config": "galactica-1.3b", "traffic": "serve-backlog",
        "chips": 1, "why": "serve-chat's lengths, all due at t=0"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "galactica-1.3b.serve-chat" in metric.get("workloads", []):
            metric["workloads"].append(cell)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    with open(os.path.join(BENCH, "traffic", "serve-chat.json")) as f:
        mix = dict(json.load(f), arrival="backlog")
    (tmp_path / "benchmarks" / "traffic" / "serve-backlog.json").write_text(
        json.dumps(mix))
    shutil.copy(os.path.join(BENCH, "limits", "galactica-1.3b.serve-chat.json"),
                tmp_path / "benchmarks" / "limits" / (cell + ".json"))
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", cell, "--seed",
         str(2**31 + 3), "--seconds", "2", "--trace", "0", "--rehearsal",
         os.path.join(HERE, "rehearsal_tiny.json")],
        cwd=tmp_path, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                 JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache")))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    with open(os.path.join(HERE, "rehearsal_tiny.json")) as f:
        tiny_rate = json.load(f)["traffic"]["rate_rps"]
    assert line["attempted"] == round(tiny_rate * 2)
    assert line["metrics"] == {}              # a rehearsal reports no metric
    assert "serve_tokens_per_s" in line["rehearsal_numbers"]
