"""The cell `trinity-mini.serve-window-edge`, rehearsed off the chip: driver,
family, reference, every reader that applies and `judge` end to end at a tiny
size that has every mechanism (`rehearsal_afmoe_tiny.json`); the float8 control
and the family's reference made wrong on purpose each turn `correct` false;
the configuration's file against the catalog row it was copied from; the
family's counts against ISSUE 36's arithmetic."""
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

CELL = "trinity-mini.serve-window-edge"
REHEARSAL = os.path.join(HERE, "rehearsal_afmoe_tiny.json")
#: the catalog's row, copied whole into the repository: the test never skips
CATALOG_ROW = os.path.join(HERE, "catalog_row_trinity_mini.json")
COUNTER_READERS = ("expert_pairs_here_share", "expert_tokens_per_step",
                   "expert_load_max_over_mean", "slot_state_share",
                   "window_positions_share", "ring_fill_share")
#: text of the reference -> the same made wrong
FAULTS = {
    "gate_dropped": ("return _mm((ctx * gate).astype(h.dtype)",
                     "return _mm(ctx.astype(h.dtype)"),
    "shared_expert_dropped": ("if arch.shared:", "if False:"),
    "full_layers_rotated": ("    if sliding:\n        q, k = _rotary",
                            "    if True:\n        q, k = _rotary"),
}


def config():
    with open(os.path.join(BENCH, "configs", "trinity-mini.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("afmoe_cell")
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns(
        "__pycache__", "*fixture.json"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def _run(root, trace, control=0, seed=2**31 + 36):
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


@pytest.mark.parametrize("reader", COUNTER_READERS)
def test_each_counter_reader_reports(traced, reader):
    value = traced["rehearsal_numbers"][reader]["value"]
    assert value > 0
    if reader == "expert_pairs_here_share":
        assert 30 < value < 70  # 4 of 8 experts are held
    if reader in ("slot_state_share", "window_positions_share"):
        assert value < 100
    if reader == "ring_fill_share":
        assert 50 < value <= 100  # a window of 12: most lanes are past it
    if reader == "expert_load_max_over_mean":
        assert value >= 1


def test_the_shared_readers_report_and_the_others_stay_silent(traced):
    for name in ("arrival_lateness_p90_ms", "queue_wait_p90_ms", "slot_occupancy",
                 "mfu.serve", "kv_reserved_unwritten"):
        assert name in traced["rehearsal_numbers"], name
    for name in ("prefix_hit_share", "flash_decode_roofline"):
        assert name not in traced["rehearsal_numbers"], name


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_made_wrong_turns_correct_false(copy, traced, fault):
    path = copy / "benchmarks" / "families" / "afmoe_reference.py"
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
    """A tree whose `HybridSpec` lacks this PR's fields (the parent's): the
    family stops with a message and a non-zero exit while it is loaded."""
    stub = tmp_path / "distributeddeeplearning_tpu" / "models"
    stub.mkdir(parents=True)
    (stub.parent / "__init__.py").write_text("")
    (stub / "__init__.py").write_text("")
    (stub / "hybrid_moe_transformer.py").write_text(
        "import dataclasses\n\nFULL, WINDOW, CONV = 0, 1, 2\n\n\n"
        "@dataclasses.dataclass(frozen=True)\nclass HybridSpec:\n"
        "    qk_norm: bool = False\n    tied_head: bool = False\n")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "3", "--rehearsal", REHEARSAL],
        cwd=copy, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path)))
    assert proc.returncode != 0
    assert "no HybridSpec.shared_width" in proc.stderr and not proc.stdout.strip()


# -- ring_fill_share and the two device-trace readers on hand-made fixtures ------------


def test_ring_fill_share_on_a_fixture():
    read = harness.load_reader("ring_fill_share")
    report = types.SimpleNamespace(window_positions_held_sum=3 * 2048 + 1000,
                                   ring_positions_capacity_sum=4 * 2048)
    assert read(types.SimpleNamespace(report=report)) == pytest.approx(
        100.0 * 7144 / 8192)
    # a report of the parent's program has no such field: nothing, no raise
    old = types.SimpleNamespace(window_positions_held_sum=5)
    assert read(types.SimpleNamespace(report=old)) is None
    assert read(types.SimpleNamespace()) is None
    empty = types.SimpleNamespace(window_positions_held_sum=0,
                                  ring_positions_capacity_sum=0)
    assert read(types.SimpleNamespace(report=empty)) is None


def _ctx(family, cfg, ops, modules, contexts):
    """A traced window of 1 s in which `contexts` tokens were decoded."""
    item = types.SimpleNamespace(uid="r0", prompt=[1] * (contexts[0] - 1))
    times = [0.0] + [0.5] * len(contexts)  # a first token, then the decoded ones
    return harness.context(
        family=family, cfg=cfg, device_kind="TPU v5 lite",
        events={"devices": {0: {"ops": ops, "modules": modules}},
                "marks": [("bench/window", 0.0, 1.0)]},
        trace_lo=0.0, trace_hi=1.0, schedule=[item], token_times={"r0": times},
        tracer=types.SimpleNamespace(t_started=0.0, t_stopped=1.0), t0=0.0)


def _step_counts(at_s, touched):
    return {"ph": "i", "name": "serve/engine.step_counts", "ts": 1e6 * at_s,
            "args": {"experts_touched_sum": touched}}


def test_the_device_trace_readers_on_a_hand_made_trace(monkeypatch):
    from distributeddeeplearning_tpu.obs import trace

    program = types.SimpleNamespace(epoch_perf_s=0.0, events=[
        _step_counts(0.2, 60), _step_counts(0.4, 64), _step_counts(1.5, 9),
        {"ph": "X", "name": "serve/engine.decode_fetch", "ts": 0.0, "dur": 1.0,
         "args": {}}])
    monkeypatch.setattr(trace, "get_tracer", lambda: program)
    cfg = config()
    family = harness.load_family(cfg)
    contexts = [3000]  # past the window: a window layer reads 2,048 of them
    ctx = _ctx(family, cfg,
               ops=[("flash_decode_decode_gqa_bfloat16.3 f32[64,32,512]", 0.1, 1e-4),
                    ("flash_decode_decode_gqa_bfloat16.3 f32[64,32,512]", 0.2, 1e-4)],
               modules=[("jit__hybrid_decode_fn(7)", 0.1, 0.006),
                        ("jit__hybrid_decode_fn(7)", 0.3, 0.006)],
               contexts=contexts)
    position = 4 * 2 * 128 * 2  # 4 KV heads of 128 + 128, bfloat16
    share = harness.load_reader("flash_decode_gqa_roofline")(ctx)
    assert share == pytest.approx(100.0 * 2 * 3000 * position / 819e9 / 2e-4)
    a_call = family.decode_step_bytes(cfg, [], 62.0)
    live = (2 * 3000 + 7 * 2048) * position
    share = harness.load_reader("decode_step_roofline")(ctx)
    assert share == pytest.approx(100.0 * (2 * a_call + live) / 819e9 / 0.012)
    assert 20 < share < 100
    program.events = program.events[-1:]
    assert harness.load_reader("decode_step_roofline")(ctx) is None


# -- the configuration's file and the family's counts ------------------------------


def test_every_published_number_is_the_catalog_rows():
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    cfg = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(c for c in manifest["configs"] if c["name"] == "trinity-mini")
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert len(row["config"]) == 32 and len(cfg["layer_types"]) == 32
    assert (cfg["num_hidden_layers"], cfg["num_experts"], cfg["vocab_size"]) == (
        9, 16, 25024)
    assert cfg["num_experts_published"] == 128 and cfg["vocab_size"] * 8 == 200192
    assert cfg["experts_held"] == list(range(16))
    assert cfg["deployment"]["chips_sharing_each_layer"] == 8
    assert set(cfg["assumed"]) >= {
        "embedding_scale", "qk_norm", "nope_full_layers", "output_gate",
        "sandwich_norms", "shared_expert", "router", "rotary_layout", "weights"}
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-mini", "serve-window-edge", 1)
    reports = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
               if CELL in m.get("workloads", [CELL])}
    lfm2 = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
            if "lfm2-8b-a1b.serve-short-chat" in m.get("workloads", ())}
    assert reports == lfm2 | {"setup_s", "window_positions_share", "ring_fill_share"}


def test_the_cut_keeps_the_dense_layer_and_two_whole_periods():
    cfg = config()
    assert cfg["layers_kept"] == [0, 4, 5, 6, 7, 8, 9, 10, 11]
    harness.load_family(cfg)  # puts the family's folder on the path
    import afmoe_weights as weights

    plan = weights.layers_of(cfg)
    assert [sliding for sliding, _ in plan] == [True] + [True, True, True, False] * 2
    assert [dense for _, dense in plan] == [True] + [False] * 8
    # the program reads the same file the same way
    from distributeddeeplearning_tpu.models import hybrid_moe_transformer as hm

    spec = hm.spec_from_config(cfg)
    assert spec.attn_kinds == tuple(
        hm.WINDOW if sliding else hm.FULL for sliding, _ in plan)
    assert spec.ffn_kinds == tuple(
        hm.DENSE if dense else hm.EXPERTS for _, dense in plan)
    assert (spec.k_dim, spec.kv_heads_full, spec.kv_heads_window,
            spec.num_q_heads, spec.window) == (128, 4, 4, 32, 2048)
    assert spec.num_experts == 128 and spec.experts_held == tuple(range(16))
    assert spec.shared_width == 1024 and spec.embed_scale == math.sqrt(2048)
    assert (spec.routed_scale, spec.topk_eps, spec.experts_per_token) == (
        2.826, 1e-20, 8)
    # and the program's shapes are the family's
    held = weights.leaf_shapes(cfg)
    for layer in range(9):
        mine = {k[2]: v for k, v in held.items()
                if k[0] == "layers" and k[1] == layer}
        assert mine == hm.layer_shapes(spec, layer)
    assert held[("head",)] == (2048, 25024) and not spec.tied_head


def test_the_counts_are_the_issues():
    cfg = config()
    harness.load_family(cfg)
    import afmoe_flops as counts
    import afmoe_weights as weights

    assert counts.expert_params(cfg) == 3 * 2048 * 1024  # 6.291 M
    assert counts.shared_params(cfg) == counts.expert_params(cfg)
    assert counts.attention_params(cfg) == pytest.approx(27.26e6, rel=1e-3)
    held = sum(math.prod(s) for s in weights.leaf_shapes(cfg).values())
    assert held == pytest.approx(1.243e9, rel=1e-3)
    # every held parameter but the norm scales and the routers' biases
    scales = 9 * (4 * 2048 + 2 * 128) + 2048 + 8 * 128
    assert counts.held_params(cfg) == held - scales
    # an expert layer here 134.5 M, the dense layer 65.0 M, embedding + head 102.5 M
    want_held = 8 * 134.5e6 + 65.0e6 + 102.5e6
    assert counts.held_params(cfg) == pytest.approx(want_held, rel=1e-3)
    # a token multiplies every attention operator with its gate, the dense
    # FFN, eight routers and shared experts, the head, and 8 x 16/128 = one
    # routed expert in each of eight layers
    want = (9 * 27.26e6 + 37.75e6 + 8 * (0.2621e6 + 6.291e6) + 51.25e6
            + 8 * 1 * 6.291e6)
    assert counts.matmul_params(cfg) == pytest.approx(want, rel=1e-3)
    short = counts.serve_token_flops(cfg, 1000) - counts.serve_token_flops(cfg, 0)
    assert short == 9 * 2 * 32 * 256 * 1000
    long = counts.serve_token_flops(cfg, 5000) - counts.serve_token_flops(cfg, 0)
    assert long == (2 * 5000 + 7 * 2048) * 2 * 32 * 256
    assert counts.kv_position_bytes(cfg) == 2048
    # weights outside the routed experts 0.77 GB; every held expert touched 2.38 GB
    assert counts.decode_step_bytes(cfg, [], 0) == pytest.approx(0.7736e9, rel=1e-3)
    whole = counts.decode_step_bytes(cfg, [], 8 * 16)
    assert whole == 2 * (counts.held_params(cfg) - 2048 * 25024)
    live = counts.decode_step_bytes(cfg, [100, 3000], 0) - counts.decode_step_bytes(
        cfg, [], 0)
    assert live == (2 * 3100 + 7 * (100 + 2048)) * 2048
    call = counts.gqa_decode_call(cfg, [100, 3000])
    assert call == {"flops": 2.0 * 32 * 256 * 3100, "bytes": 3100 * 2048}
    assert counts.full_layers(cfg) == 2 and counts.window_layers(cfg) == 7


def test_the_traffic_file_holds_the_issues_parameters():
    import numpy as np

    import traffic_gen

    mix = harness.load_traffic("serve-window-edge")
    assert (mix["kind"], mix["loop"], mix["arrival"]) == ("serve", "open", "poisson")
    assert mix["schedule_seed"] == 36 and mix["shared_prefix_tokens"] == 0
    assert mix["tail"] == {"dist": "lognormal", "median": 1536, "sigma": 0.7,
                           "min": 128, "max": 7168}
    assert mix["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.7,
                             "min": 32, "max": 768}
    assert mix["sampling"] == "greedy" and mix["eos_id"] is None
    assert mix["drain_limit_s"] == 60
    with open(os.path.join(BENCH, "traffic", "serve-mixed-len.json")) as f:
        mimo = json.load(f)
    for key in ("trace_window_share", "check_sample_requests"):
        assert mix[key] == mimo[key]
    knee, share = mix["knee_rps"], mix["knee_share"]
    assert share in (0.6, 0.5) and mix["rate_rps"] == pytest.approx(
        share * knee, abs=0.05)
    cfg = config()
    geo = cfg["serving"]
    assert mix["tail"]["max"] + mix["output"]["max"] <= geo["max_seq"]
    assert geo["kv_pages"] == geo["batch_slots"] * geo["max_seq"] // geo["page_size"]
    # the stated quantiles: median 1,536, a third past the window on arrival,
    # the 90th percentile about 3,800; outputs of a paragraph
    plan = traffic_gen.serve_schedule(mix, vocab_size=cfg["vocab_size"],
                                      seed=2**31 + 36, seconds=50)
    prompts = np.array([len(item.prompt) for item in plan])
    outputs = np.array([item.max_new_tokens for item in plan])
    assert len(plan) == round(50 * mix["rate_rps"])
    assert np.median(prompts) == pytest.approx(1536, rel=0.02)
    assert 0.30 < (prompts > cfg["sliding_window"]).mean() < 0.38
    assert 3500 < np.percentile(prompts, 90) < 4100
    assert prompts.min() >= 128 and prompts.max() == 7168
    assert np.median(outputs) == pytest.approx(192, rel=0.03)
    assert outputs.min() >= 32 and outputs.max() <= 768
    assert max(max(item.prompt) for item in plan) < cfg["vocab_size"]
