"""The cell `jamba2-3b.serve-long-context`, rehearsed off the chip: load
generator, family, reference, every reader that applies and `judge` end to
end at a tiny size that has every mechanism (`rehearsal_jamba_tiny.json`); the float8
control and the family's reference made wrong on purpose each turn `correct`
false; the configuration's file against the catalog row it was copied from;
the family's counts against hand arithmetic; `ssm_scan_roofline` on a
hand-made trace."""
import json
import math
import os
import shutil
import subprocess
import sys
import types

import pytest
from conftest import BENCH, HERE, ROOT

import harness

CELL = "jamba2-3b.serve-long-context"
REHEARSAL = os.path.join(HERE, "rehearsal_jamba_tiny.json")
#: the catalog's row, copied whole into the repository: the test never skips
CATALOG_ROW = os.path.join(HERE, "catalog_row_jamba2_3b.json")
#: text of the reference -> the same made wrong
FAULTS = {
    "gate_dropped": ("(y * jax.nn.silu(z.astype(f32)))", "y"),
    "skip_dropped": ("y = y + dd * u", "y = y"),
    "step_norm_dropped": ('dt = _rms_norm(dbc[:, :r], p["dt_norm"], arch.eps)',
                          "dt = dbc[:, :r]"),
}


def config():
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("jamba_cell")
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns(
        "__pycache__", "*fixture.json"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def _run(root, trace, control=0, seed=2**31 + 41):
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "3", "--trace", str(trace), "--control",
         str(control), "--rehearsal", REHEARSAL],
        cwd=root, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                 JAX_COMPILATION_CACHE_DIR=str(root / "cache")))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def traced(copy):
    return _run(copy, trace=1, control=1)


def test_the_cell_rehearses_end_to_end_and_is_correct(traced):
    assert traced["correct"] is True and traced["failed"] == 0
    assert traced["attempted"] >= 6 and traced["metrics"] == {}
    checks = traced["checks"]
    assert checks["tokens_compared"]["value"] >= 20
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["logit_std"]["value"] > 0.3  # logits apart: a fault shows


def test_the_control_in_the_program_s_place_is_not_correct(traced):
    control = traced["stand_ins"]["control"]
    assert control["correct"] is False
    gap = control["checks"]["token_gap_max"]
    assert gap["value"] > 100 * gap["limit"]


def test_the_shared_readers_report_and_the_others_stay_silent(traced):
    numbers = traced["rehearsal_numbers"]
    for name in ("arrival_lateness_p90_ms", "queue_wait_p90_ms", "slot_occupancy",
                 "mfu.serve", "kv_reserved_unwritten", "slot_state_share"):
        assert name in numbers, name
    # 4 state-space layers of 3 x 64 + 16 x 64 values a slot against 64 B of
    # K/V a position: the state is most of what a live lane holds
    assert 80 < numbers["slot_state_share"]["value"] < 100
    for name in ("prefix_hit_share", "flash_decode_roofline",
                 "expert_pairs_here_share", "window_positions_share"):
        assert name not in numbers, name


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_made_wrong_turns_correct_false(copy, traced, fault):
    path = copy / "benchmarks" / "families" / "jamba_reference.py"
    sound = path.read_text()
    right, wrong_text = FAULTS[fault]
    assert sound.count(right) == 1
    path.write_text(sound.replace(right, wrong_text))
    try:
        wrong = _run(copy, trace=0)
    finally:
        path.write_text(sound)
    assert wrong["correct"] is False and wrong["failed"] == 0
    gap = wrong["checks"]["token_gap_max"]
    assert gap["value"] > 100 * gap["limit"]
    assert traced["checks"]["token_gap_max"]["value"] <= gap["limit"]


def test_the_parent_s_program_stops_the_cell_before_any_device_work(
        copy, tmp_path):
    """A tree whose model module has no state-space kind (the parent's): the
    family stops with a message and a non-zero exit while it is loaded."""
    stub = tmp_path / "distributeddeeplearning_tpu" / "models"
    stub.mkdir(parents=True)
    (stub.parent / "__init__.py").write_text("")
    (stub / "__init__.py").write_text("")
    (stub / "hybrid_moe_transformer.py").write_text(
        "import dataclasses\n\nFULL, WINDOW, CONV = 0, 1, 2\n\n\n"
        "@dataclasses.dataclass(frozen=True)\nclass HybridSpec:\n"
        "    rotate_full: bool = True\n    tied_head: bool = False\n")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "3", "--rehearsal", REHEARSAL],
        cwd=copy, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path)))
    assert proc.returncode != 0
    assert "no MAMBA, mamba_mixer, HybridSpec.ssm" in proc.stderr
    assert not proc.stdout.strip()


# -- ssm_scan_roofline on a hand-made trace ------------------------------------------


def _dispatch(at_s, chunk):
    return {"ph": "X", "name": "serve/engine.chunk_dispatch", "ts": 1e6 * at_s,
            "dur": 100.0, "args": {"chunk": chunk, "real": chunk - 3, "offset": 0}}


def test_ssm_scan_roofline_on_a_hand_made_trace(monkeypatch):
    from distributeddeeplearning_tpu.obs import trace

    program = types.SimpleNamespace(epoch_perf_s=0.0, events=[
        _dispatch(0.2, 512), _dispatch(0.5, 256), _dispatch(1.5, 128),
        {"ph": "X", "name": "serve/decode_step", "ts": 0.0, "dur": 1.0, "args": {}}])
    monkeypatch.setattr(trace, "get_tracer", lambda: program)
    cfg = config()
    family = harness.load_family(cfg)
    kernel = "ssm_scan_chunk_float32.7"
    ops = [(kernel, 0.2 + 1e-3 * i, 1e-4) for i in range(26)] + [
        (kernel, 0.5 + 1e-3 * i, 6e-5) for i in range(26)] + [
        ("fusion.3 f32[512,5120]", 0.3, 1e-3)]
    ctx = harness.context(
        family=family, cfg=cfg, device_kind="TPU v5 lite",
        events={"devices": {0: {"ops": ops, "modules": []}},
                "marks": [("bench/window", 0.0, 1.0)]},
        trace_lo=0.0, trace_hi=1.0,
        tracer=types.SimpleNamespace(t_started=0.0, t_stopped=1.0), t0=0.0)
    share = harness.load_reader("ssm_scan_roofline")(ctx)
    # the two chunks inside the window, 384 rows on the mean, one call a layer each
    a_call = 4.0 * (384 * (3 * 5120 + 2 * 16) + 3 * 16 * 5120 + 5120)
    assert share == pytest.approx(100.0 * 52 * a_call / 819e9 / (26 * 1.6e-4))
    assert 0 < share < 100
    # a parent's trace has no such kernel: nothing, no raise
    ctx.events = {"devices": {0: {"ops": ops[-1:], "modules": []}}, "marks": []}
    assert harness.load_reader("ssm_scan_roofline")(ctx) is None
    program.events = []
    ctx.events = {"devices": {0: {"ops": ops, "modules": []}}, "marks": []}
    assert harness.load_reader("ssm_scan_roofline")(ctx) is None


# -- the configuration's file and the family's counts ------------------------------


def test_every_published_number_is_the_catalog_rows():
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    cfg = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == "jamba2-3b")
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == []
    for key, value in row["config"].items():
        assert cfg[key] == value, key
    assert len(row["config"]) == 26
    assert cfg["deployment"]["chips_sharing_each_layer"] == 1
    assert set(cfg["assumed"]) >= {"layer_order", "inner_norms", "no_positions",
                                   "ssm_state_dtype", "weights"}
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2-3b", "serve-long-context", 1)
    reports = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    lfm2 = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
            if "lfm2-8b-a1b.serve-short-chat" in m.get("workloads", ())}
    experts = {"expert_pairs_here_share", "expert_tokens_per_step",
               "expert_load_max_over_mean"}
    assert reports == (lfm2 - experts) | {"setup_s", "ssm_scan_roofline"}


def test_the_counts_are_the_issues():
    cfg = config()
    harness.load_family(cfg)
    import jamba_flops as counts
    import jamba_weights as weights

    assert weights.is_attention(cfg).count(True) == 2
    assert [i for i, a in enumerate(weights.is_attention(cfg)) if a] == [7, 21]
    held = sum(math.prod(s) for s in weights.leaf_shapes(cfg).values())
    assert held == counts.held_params(cfg)
    # a Mamba layer 104.16 M (mixer 41.24 M, FFN 62.91 M), an attention layer
    # 76.68 M (13.76 M + the FFN), the embedding 167.77 M: 3.029 B
    mixer = sum(math.prod(s) for s in weights.mixer_shapes(cfg).values())
    assert mixer == pytest.approx(41.24e6, rel=1e-3)
    assert counts.attention_params(cfg) == pytest.approx(13.76e6, rel=1e-3)
    assert counts.ffn_params(cfg) == pytest.approx(62.91e6, rel=1e-3)
    want = 26 * (mixer + 62.91e6 + 2 * 2560) + 2 * (13.76e6 + 62.91e6) + 167.77e6
    assert held == pytest.approx(want, rel=1e-4)
    assert held == pytest.approx(3.029e9, rel=1e-3)
    assert counts.matmul_params(cfg) == pytest.approx(3.026e9, rel=1e-3)
    # per slot: 26 x (3 x 5120 x 2 B + 16 x 5120 x 4 B); a position: 1,024 B
    assert counts.lane_state_bytes(cfg) == 798_720 + 8_519_680 == 9_318_400
    assert counts.kv_position_bytes(cfg) * counts.full_layers(cfg) == 1024
    short = counts.serve_token_flops(cfg, 1000) - counts.serve_token_flops(cfg, 0)
    assert short == 2 * 2 * 20 * 256 * 1000
    # a decode step reads every weight, each live lane's K/V, and reads and
    # writes its state
    assert counts.decode_step_bytes(cfg, []) == 2 * held
    live = counts.decode_step_bytes(cfg, [100, 3000]) - counts.decode_step_bytes(cfg, [])
    assert live == 3100 * 1024 + 2 * 2 * 9_318_400
    assert counts.gqa_decode_call(cfg, [100, 3000]) == {
        "flops": 2.0 * 20 * 256 * 3100, "bytes": 3100 * 512}
    # a 512-row chunk's scan call: 31.5 MB of rows, 1.0 MB of A, D and the
    # state in and out
    call = counts.ssm_scan_call(cfg, 512)["bytes"]
    assert call == 4 * (512 * (3 * 5120 + 32) + 3 * 16 * 5120 + 5120)
    assert call == pytest.approx(32.5e6, rel=1e-2)


def test_the_traffic_file_holds_the_issues_parameters():
    import numpy as np

    import traffic_gen

    mix = harness.load_traffic("serve-long-context")
    assert (mix["kind"], mix["loop"], mix["arrival"]) == ("serve", "open", "poisson")
    assert mix["schedule_seed"] == 41 and mix["shared_prefix_tokens"] == 0
    assert mix["tail"] == {"dist": "lognormal", "median": 2048, "sigma": 1.1,
                           "min": 256, "max": 32768}
    assert mix["output"] == {"dist": "lognormal", "median": 256, "sigma": 0.7,
                             "min": 32, "max": 1024}
    assert mix["sampling"] == "greedy" and mix["eos_id"] is None
    assert mix["check_sample_requests"] == 6
    knee, share = mix["knee_rps"], mix["knee_share"]
    assert share in (0.5, 0.4) and mix["rate_rps"] == pytest.approx(
        share * knee, abs=0.05)
    cfg = config()
    geo = cfg["serving"]
    assert mix["tail"]["max"] + mix["output"]["max"] == geo["max_seq"]
    assert geo["kv_pages"] == geo["batch_slots"] * geo["max_seq"] // geo["page_size"]
    # the stated quantiles over a long schedule: median 2,048, one in ten past
    # 8.4k (17 or more chunks), every prompt over one chunk's 512 but the
    # shortest fifth; outputs median 256
    plan = traffic_gen.serve_schedule(dict(mix, rate_rps=20.0), vocab_size=cfg["vocab_size"],
                                      seed=2**31 + 41, seconds=50)
    prompts = np.array([len(item.prompt) for item in plan])
    outputs = np.array([item.max_new_tokens for item in plan])
    assert len(plan) == 1000
    assert np.median(prompts) == pytest.approx(2048, rel=0.02)
    assert 0.08 < (prompts > 8448).mean() < 0.12
    assert 0.85 < (prompts > 512).mean() < 0.92
    assert prompts.min() >= 256 and prompts.max() == 32768
    assert np.median(outputs) == pytest.approx(256, rel=0.03)
    assert outputs.min() >= 32 and outputs.max() <= 1024
    assert max(max(item.prompt) for item in plan) < cfg["vocab_size"]
