"""The cell `mimo-v2-flash.serve-mixed-len`, rehearsed off the chip: driver,
family, reference, every new reader and `judge` end to end at a tiny size that
has every mechanism (`rehearsal_mimo_tiny.json`); the family's reference, made
wrong on purpose, turns `correct` false; the configuration's file against the
catalog row it was copied from; the family's counts against the issue's."""
import json
import os
import shutil
import subprocess
import sys
import types

import pytest
from conftest import BENCH, HERE, ROOT

import harness

CELL = "mimo-v2-flash.serve-mixed-len"
REHEARSAL = os.path.join(HERE, "rehearsal_mimo_tiny.json")
#: the catalog's row, copied whole into the repository: the test never skips
CATALOG_ROW = os.path.join(HERE, "catalog_row_mimo_v2_flash.json")
COUNTER_READERS = ("expert_pairs_here_share", "expert_tokens_per_step",
                   "expert_load_max_over_mean", "window_positions_share")
#: text of the reference -> the same made wrong
FAULTS = {
    "sink_dropped": ('p["sink"] if has_sink else None', "None"),
    "one_expert_halved": (
        "weight = jnp.where(mine, weights, 0.0).sum(-1)",
        "weight = jnp.where(mine, weights, 0.0).sum(-1) * (0.5 if slot == 0 else 1.0)"),
}


def config():
    with open(os.path.join(BENCH, "configs", "mimo-v2-flash.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def copy(tmp_path_factory):
    root = tmp_path_factory.mktemp("mimo_cell")
    shutil.copytree(BENCH, root / "benchmarks", ignore=shutil.ignore_patterns(
        "__pycache__", "*fixture.json"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    return root


def _run(root, trace, seed=2**31 + 30):
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "3", "--trace", str(trace), "--rehearsal",
         REHEARSAL],
        cwd=root, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
                 JAX_COMPILATION_CACHE_DIR=str(root / "cache")))
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def traced(copy):
    return _run(copy, trace=1)


def test_the_cell_rehearses_end_to_end_and_is_correct(traced):
    assert traced["correct"] is True and traced["failed"] == 0
    assert traced["attempted"] >= 6 and traced["metrics"] == {}
    checks = traced["checks"]
    assert checks["tokens_compared"]["value"] >= 20
    assert checks["compiles_in_window"]["value"] == 0
    assert checks["logit_std"]["value"] > 0.3  # logits apart: a fault shows


@pytest.mark.parametrize("reader", COUNTER_READERS)
def test_each_counter_reader_reports(traced, reader):
    value = traced["rehearsal_numbers"][reader]["value"]
    assert value > 0
    if reader == "expert_pairs_here_share":
        assert 10 < value < 45  # 4 of 16 held: 25 expected, few tokens
    if reader == "window_positions_share":
        assert value < 100
    if reader == "expert_load_max_over_mean":
        assert value >= 1


def test_the_shared_readers_report_too(traced):
    for name in ("arrival_lateness_p90_ms", "queue_wait_p90_ms", "slot_occupancy",
                 "mfu.serve", "kv_reserved_unwritten"):
        assert name in traced["rehearsal_numbers"], name


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_the_reference_made_wrong_turns_correct_false(copy, traced, fault):
    path = copy / "benchmarks" / "families" / "mimo_v2_reference.py"
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

    # two steps counted inside the traced second and one after it, which a
    # mean over the whole run would take in
    program = types.SimpleNamespace(epoch_perf_s=0.0, events=[
        _step_counts(0.2, 28), _step_counts(0.4, 32), _step_counts(1.5, 90),
        {"ph": "X", "name": "serve/engine.decode_fetch", "ts": 0.0, "dur": 1.0,
         "args": {}}])
    monkeypatch.setattr(trace, "get_tracer", lambda: program)
    cfg = config()
    family = harness.load_family(cfg)  # puts the family's folder on the path
    import mimo_v2_flops as counts

    run = family.run_config(cfg)
    contexts = [1000]
    ctx = _ctx(family, cfg,
               ops=[("flash_decode_decode_gqa_bfloat16.3 f32[48,64,512]", 0.1, 1e-4),
                    ("flash_decode_decode_gqa_bfloat16.3 f32[48,64,512]", 0.2, 1e-4)],
               modules=[("jit__hybrid_decode_fn(7)", 0.1, 0.01),
                        ("jit__hybrid_decode_fn(7)", 0.3, 0.01)],
               contexts=contexts)
    kv = 2 * 1000 * 4 * 320 * 2  # two full layers, 4 KV heads of 192 + 128, bf16
    share = harness.load_reader("flash_decode_gqa_roofline")(ctx)
    assert share == pytest.approx(100.0 * kv / 819e9 / 2e-4)
    window_kv = 5 * 128 * 8 * 320 * 2  # five window layers, capped at the window
    a_call = counts.decode_step_bytes(run, [], 30.0)
    assert a_call == pytest.approx(2 * (
        2 * 89.13e6 + 5 * 94.37e6 + 201.33e6 + 6 * 1.0486e6 + 78.12e6
        + 30 * 25.166e6), rel=1e-3)
    share = harness.load_reader("decode_step_roofline")(ctx)
    assert share == pytest.approx(
        100.0 * (2 * a_call + kv + window_kv) / 819e9 / 0.02)
    assert 0 < share < 100
    # a program that records no step counts reads nothing, and neither does
    # one without the family's counts (the parent's)
    program.events = program.events[-1:]
    assert harness.load_reader("decode_step_roofline")(ctx) is None
    bare = types.SimpleNamespace(PROGRAMS=family.PROGRAMS)
    ctx.family = bare
    assert harness.load_reader("decode_step_roofline")(ctx) is None
    assert harness.load_reader("flash_decode_gqa_roofline")(ctx) is None


# -- the configuration's file and the family's counts ------------------------------


def test_every_published_number_is_the_catalog_rows():
    with open(CATALOG_ROW) as f:
        row = json.load(f)
    cfg = config()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == "mimo-v2-flash")
    assert entry["source"] == row["source_url"] == cfg["source"]
    assert sorted(entry["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                        "vocab_size"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert cfg[key] != value and cfg["published"][key] == value
        else:
            assert cfg[key] == value, key
    assert cfg["num_hidden_layers"] == len(cfg["layers_kept"]) == 7
    assert cfg["n_routed_experts"] == len(cfg["experts_held"]) == 16
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"]


def test_the_cut_keeps_layer_0_and_one_whole_period():
    cfg = config()
    run = harness.load_family(cfg).run_config(cfg)
    assert run["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert run["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert len(cfg["hybrid_layer_pattern"]) == len(cfg["moe_layer_freq"]) == 48


def test_the_counts_are_the_issues():
    cfg = config()
    run = harness.load_family(cfg).run_config(cfg)
    import mimo_v2_flops as counts
    import mimo_v2_weights as weights

    assert counts.attention_params(run, False) == pytest.approx(89.13e6, rel=1e-4)
    assert counts.attention_params(run, True) == pytest.approx(94.37e6, rel=1e-4)
    assert counts.expert_params(run) == 3 * 4096 * 2048
    held = sum(__import__("math").prod(s) for s in weights.leaf_shapes(run).values())
    assert held == pytest.approx(3.430e9, rel=1e-3)
    # a token multiplies attention, the dense FFN, six routers, the head and
    # 8 x 16/256 experts in each of six layers
    want = (2 * 89.13e6 + 5 * 94.37e6 + 201.33e6 + 6 * 1.0486e6 + 78.12e6
            + 6 * 0.5 * 25.166e6)
    assert counts.matmul_params(run) == pytest.approx(want, rel=1e-3)
    near = counts.serve_token_flops(run, 100) - 2 * counts.matmul_params(run)
    far = counts.serve_token_flops(run, 10000) - 2 * counts.matmul_params(run)
    assert near == 2 * 64 * 320 * 100 * 7
    assert far == 2 * 64 * 320 * (2 * 10000 + 5 * 128)
    assert counts.kv_position_bytes(run, False) == 2560
    assert counts.kv_position_bytes(run, True) == 5120
