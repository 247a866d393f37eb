"""The cell `lfm2-8b-a1b.serve-short-chat`, rehearsed off the chip: driver,
family, reference, every reader that applies and `judge` end to end at a tiny
size that has every mechanism (`rehearsal_lfm2_tiny.json`); the float8 control
and the family's reference made wrong on purpose each turn `correct` false;
the configuration's file against the catalog row it was copied from; the
family's counts against ISSUE 34's arithmetic."""
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

CELL = "lfm2-8b-a1b.serve-short-chat"
REHEARSAL = os.path.join(HERE, "rehearsal_lfm2_tiny.json")
#: the catalog's row, copied whole into the repository: the test never skips
CATALOG_ROW = os.path.join(HERE, "catalog_row_lfm2_8b_a1b.json")
COUNTER_READERS = ("expert_pairs_here_share", "expert_tokens_per_step",
                   "expert_load_max_over_mean", "slot_state_share")
#: text of the reference -> the same made wrong
FAULTS = {
    "oldest_tap_dropped": ("for j in range(arch.taps):",
                           "for j in range(1, arch.taps):"),
    "qk_norm_dropped": (
        'q = _rotary(_rms_norm(q, p["q_norm"], arch.eps).astype(h.dtype), arch.theta)',
        "q = _rotary(q, arch.theta)"),
    "bias_weighs": ("weights = jnp.take_along_axis(scores, chosen, -1)",
                    "weights = jnp.take_along_axis("
                    'scores + p["router_bias"].astype(jnp.float32), chosen, -1)'),
}


def config():
    with open(os.path.join(BENCH, "configs", "lfm2-8b-a1b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("lfm2_cell")
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns(
        "__pycache__", "*fixture.json"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def _run(root, trace, control=0, seed=2**31 + 34):
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
        assert value == 100.0  # every expert is held
    if reader == "slot_state_share":
        assert value < 100
    if reader == "expert_load_max_over_mean":
        assert value >= 1


def test_the_shared_readers_report_and_the_others_stay_silent(traced):
    for name in ("arrival_lateness_p90_ms", "queue_wait_p90_ms", "slot_occupancy",
                 "mfu.serve", "kv_reserved_unwritten"):
        assert name in traced["rehearsal_numbers"], name
    for name in ("prefix_hit_share", "window_positions_share",
                 "flash_decode_roofline"):
        assert name not in traced["rehearsal_numbers"], name


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_made_wrong_turns_correct_false(copy, traced, fault):
    path = copy / "benchmarks" / "families" / "lfm2_moe_reference.py"
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


def test_a_tree_whose_program_has_no_conv_layers_stops_before_any_device_work(
        copy, tmp_path):
    """The parent's program: the family stops with a message and a non-zero
    exit while it is loaded."""
    stub = tmp_path / "distributeddeeplearning_tpu" / "models"
    stub.mkdir(parents=True)
    (stub.parent / "__init__.py").write_text("")
    (stub / "__init__.py").write_text("")
    (stub / "hybrid_moe_transformer.py").write_text("FULL, WINDOW = 0, 1\n")
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed", "1",
         "--seconds", "3", "--rehearsal", REHEARSAL],
        cwd=copy, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(tmp_path)))
    assert proc.returncode != 0
    assert "no convolution layer kind" in proc.stderr and not proc.stdout.strip()


# -- the two device-trace readers on a hand-made trace ----------------------------


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
        _step_counts(0.2, 380), _step_counts(0.4, 384), _step_counts(1.5, 90),
        {"ph": "X", "name": "serve/engine.decode_fetch", "ts": 0.0, "dur": 1.0,
         "args": {}}])
    monkeypatch.setattr(trace, "get_tracer", lambda: program)
    cfg = config()
    family = harness.load_family(cfg)
    contexts = [300]
    ctx = _ctx(family, cfg,
               ops=[("flash_decode_decode_gqa_bfloat16.3 f32[64,32,512]", 0.1, 1e-4),
                    ("flash_decode_decode_gqa_bfloat16.3 f32[64,32,512]", 0.2, 1e-4)],
               modules=[("jit__hybrid_decode_fn(7)", 0.1, 0.02),
                        ("jit__hybrid_decode_fn(7)", 0.3, 0.02)],
               contexts=contexts)
    kv = 3 * 300 * 8 * 128 * 2  # three attention layers, 8 KV heads of 64 + 64
    share = harness.load_reader("flash_decode_gqa_roofline")(ctx)
    assert share == pytest.approx(100.0 * kv / 819e9 / 2e-4)
    a_call = family.decode_step_bytes(cfg, [], 382.0)
    state = 2 * 11 * 2 * 2048 * 2  # a live lane's state, read and written
    share = harness.load_reader("decode_step_roofline")(ctx)
    assert share == pytest.approx(
        100.0 * (2 * a_call + kv + state) / 819e9 / 0.04)
    assert 50 < share < 100  # two steps that touch all but two experts
    program.events = program.events[-1:]
    assert harness.load_reader("decode_step_roofline")(ctx) is None


# -- the configuration's file and the family's counts ------------------------------


def test_every_published_number_is_the_catalog_rows():
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    cfg = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "lfm2-8b-a1b")
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert entry["reduced"] == ["num_hidden_layers"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert len(row["config"]) == 20 and len(cfg["layer_types"]) == 24
    assert cfg["num_experts"] == 32 and cfg["vocab_size"] == 65536
    assert "experts_held" not in cfg  # all of them
    assert cfg["deployment"]["pipeline_stages"] == 2
    assert set(cfg["assumed"]) >= {"head_dim", "tie_word_embeddings", "qk_norm",
                                   "conv_state", "router"}


def test_the_cut_keeps_layers_0_to_13():
    cfg = config()
    assert cfg["layers_kept"] == list(range(14)) and cfg["num_hidden_layers"] == 14
    harness.load_family(cfg)  # puts the family's folder on the path
    import lfm2_moe_weights as weights

    plan = weights.layers_of(cfg)
    assert [op for op, _ in plan] == ["conv", "conv"] + [
        "full_attention", "conv", "conv", "conv"] * 3
    assert [dense for _, dense in plan] == [True, True] + [False] * 12
    # the program reads the same file the same way
    from distributeddeeplearning_tpu.models import hybrid_moe_transformer as hm

    spec = hm.spec_from_config(cfg)
    assert spec.attn_kinds == tuple(
        hm.CONV if op == "conv" else hm.FULL for op, _ in plan)
    assert spec.ffn_kinds == tuple(
        hm.DENSE if dense else hm.EXPERTS for _, dense in plan)
    assert (spec.k_dim, spec.kv_heads_full, spec.num_q_heads) == (64, 8, 32)
    assert len(spec.experts_held) == spec.num_experts == 32
    # and the program's shapes are the family's
    held = weights.leaf_shapes(cfg)
    for layer in range(14):
        for name, shape in hm.layer_shapes(spec, layer).items():
            assert held[("layers", layer, name)] == shape
    assert ("head",) not in held and spec.tied_head


def test_the_counts_are_the_issues():
    cfg = config()
    harness.load_family(cfg)
    import lfm2_moe_flops as counts
    import lfm2_moe_weights as weights

    assert counts.expert_params(cfg) == 3 * 2048 * 1792  # 11.01 M
    assert counts.conv_params(cfg) == pytest.approx(16.78e6, rel=1e-3)
    assert counts.attention_params(cfg) == pytest.approx(10.49e6, rel=1e-3)
    held = sum(math.prod(s) for s in weights.leaf_shapes(cfg).values())
    assert held == pytest.approx(4.667e9, rel=1e-3)
    # a token multiplies every operator, the two dense FFNs, twelve routers,
    # the head and 4 of 32 experts in each of twelve layers
    want = (11 * 16.78e6 + 3 * 10.49e6 + 2 * 44.04e6 + 12 * 65536 + 134.2e6
            + 12 * 4 * 11.01e6)
    assert counts.matmul_params(cfg) == pytest.approx(want, rel=1e-3)
    attn = counts.serve_token_flops(cfg, 1000) - counts.serve_token_flops(cfg, 0)
    assert attn == 3 * 2 * 32 * 128 * 1000
    assert counts.kv_position_bytes(cfg) * 3 == 6144
    assert counts.slot_state_bytes(cfg) == 11 * 2 * 2048 * 2 == 90112
    # a decode step that touches every expert reads every weight: 9.33 GB
    # (and the taps), 11.4 ms at 819 GB/s
    whole = counts.decode_step_bytes(cfg, [], 12 * 32)
    assert whole == pytest.approx(9.33e9, rel=1e-3)
    # every held parameter but the norm scales and the routers' biases
    assert whole == 2 * (held - 29 * 2048 - 3 * 128 - 12 * 32)
    live = counts.decode_step_bytes(cfg, [100, 300], 0) - counts.decode_step_bytes(
        cfg, [], 0)
    assert live == 400 * 6144 + 2 * 2 * 90112
    call = counts.gqa_decode_call(cfg, [100, 300])
    assert call == {"flops": 2.0 * 32 * 128 * 400, "bytes": 400 * 2048}
    assert counts.full_layers(cfg) == 3 and counts.conv_layers(cfg) == 11


def test_the_traffic_file_holds_the_issues_parameters():
    mix = harness.load_traffic("serve-short-chat")
    assert (mix["kind"], mix["loop"], mix["arrival"]) == ("serve", "open", "poisson")
    assert mix["schedule_seed"] == 34 and mix["shared_prefix_tokens"] == 0
    assert mix["tail"] == {"dist": "lognormal", "median": 192, "sigma": 1.0,
                           "min": 16, "max": 2048}
    assert mix["output"] == {"dist": "lognormal", "median": 192, "sigma": 0.7,
                             "min": 16, "max": 512}
    assert mix["sampling"] == "greedy" and mix["eos_id"] is None
    assert mix["drain_limit_s"] == 60
    with open(os.path.join(BENCH, "traffic", "serve-mixed-len.json")) as f:
        mimo = json.load(f)
    for key in ("trace_window_share", "check_sample_requests"):
        assert mix[key] == mimo[key]
    knee, share = mix["knee_rps"], mix["knee_share"]
    assert share in (0.65, 0.5) and mix["rate_rps"] == pytest.approx(
        share * knee, abs=0.05)
    geo = config()["serving"]
    assert mix["tail"]["max"] + mix["output"]["max"] <= geo["max_seq"]
    assert geo["kv_pages"] == geo["batch_slots"] * geo["max_seq"] // geo["page_size"]
