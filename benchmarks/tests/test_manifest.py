"""Every file the manifest names exists; every name and unit is made of the
permitted characters; every metric's cells report what it moves."""
import json
import os
import re

from conftest import BENCH, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_names():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in m[k]]
    assert all(NAME.match(n) for n in names), names
    for k in ("configs", "workloads"):
        ns = [x["name"] for x in m[k]]
        assert len(ns) == len(set(ns))
    metric_names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(metric_names) == len(set(metric_names))
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
    for x in m["end_to_end"]:
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
        assert set(x) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert any(x["name"] == "setup_s" and "workloads" not in x for x in m["end_to_end"])


def test_files_exist():
    m = manifest()
    for c in m["configs"]:
        assert c["file"].startswith(m["paths"][0] + "/")
        assert os.path.isfile(os.path.join(ROOT, c["file"])), c["file"]
        assert all(NAME.match(k) for k in c["reduced"])
    used = {w["config"] for w in m["workloads"]}
    assert used == {c["name"] for c in m["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(pairs) == len(set(pairs))
    for w in m["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200 and "\n" not in w["why"]
        assert os.path.isfile(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "limits", w["name"] + ".json"))
    for p in m["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics", p["name"] + ".py")), p
        assert set(p) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}


def test_every_cell_reports_what_it_should():
    m = manifest()
    e2e = {x["name"]: x for x in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    for p in m["per_layer"]:
        assert p["moves"] in e2e and p["moves"] != "setup_s"
        assert cells_of(p) <= cells_of(e2e[p["moves"]]), p["name"]
        assert cells_of(p) <= cells
    for w in cells:
        assert any(w in cells_of(x) for x in m["end_to_end"] if x["name"] != "setup_s")
        assert any(w in cells_of(x) for x in m["per_layer"])
    four = sum(1 for w in m["workloads"] if w["chips"] == 4)
    assert four <= max(len(m["workloads"]) // 4, 1)
    for p in m["per_layer"]:
        if p["name"].endswith("_roofline"):
            whole = [q for q in m["per_layer"] if "mfu" in re.split(r"[._]", q["name"])
                     and q["moves"] == p["moves"]]
            assert whole, f"{p['name']} has no whole-step mfu beside it"
