"""`ssm_step_roofline` of the cell `jamba2-3b.serve-long-context`: on a
hand-made trace it is the share computed by hand, and it says nothing, and
raises nothing, where the trace holds no decode step kernel (a parent's)."""
import json
import os
import types

import pytest
from conftest import BENCH, ROOT

import harness

CELL = "jamba2-3b.serve-long-context"
KERNEL = "ssm_decode_step_float32.4"
DECODE = "jit__hybrid_decode_fn(12)"


def config():
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def _ctx(ops, modules, decoded):
    """A traced window of 1 s in which `decoded` tokens were decoded (and
    one more, the request's first, came from its prefill)."""
    cfg = config()
    item = types.SimpleNamespace(uid="r0", prompt=[1] * 2000)
    return harness.context(
        family=harness.load_family(cfg), cfg=cfg, device_kind="TPU v5 lite",
        events={"devices": {0: {"ops": ops, "modules": modules}},
                "marks": [("bench/window", 0.0, 1.0)]},
        trace_lo=0.0, trace_hi=1.0, schedule=[item],
        token_times={"r0": [0.0] + [0.5] * decoded},
        tracer=types.SimpleNamespace(t_started=0.0, t_stopped=1.0), t0=0.0)


def test_the_share_is_the_hand_computed_one():
    # two decode steps of 26 state-space layers, 18 tokens decoded: 9 live
    # lanes a call, each call 40 us
    ops = [(KERNEL, 0.1 + 1e-3 * i, 4e-5) for i in range(52)] + [
        ("fusion.7 f32[64,5120]", 0.5, 1e-3)]
    modules = [(DECODE, 0.1, 0.02), (DECODE, 0.3, 0.02)]
    share = harness.load_reader("ssm_step_roofline")(_ctx(ops, modules, 18))
    lane = 4.0 * (2 * 16 * 5120 + 3 * 5120 + 2 * 16)  # 716,928 B
    assert share == pytest.approx(100.0 * 52 * 9 * lane / 819e9 / (52 * 4e-5))
    assert 0 < share < 100


def test_no_kernel_in_the_trace_reads_nothing():
    modules = [(DECODE, 0.1, 0.02)]
    ops = [("add_select_fusion.3", 0.1, 2e-4)]
    assert harness.load_reader("ssm_step_roofline")(_ctx(ops, modules, 9)) is None
    # no decode program or no token decoded in the window: nothing either
    kernel = [(KERNEL, 0.1, 4e-5)]
    assert harness.load_reader("ssm_step_roofline")(_ctx(kernel, [], 9)) is None
    assert harness.load_reader("ssm_step_roofline")(
        _ctx(kernel, modules, 0)) is None


def test_the_manifest_entry_names_the_jamba_cell_alone():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"]
                 if m["name"] == "ssm_step_roofline")
    assert entry == {"name": "ssm_step_roofline", "unit": "%",
                     "better": "higher", "source": "device_trace",
                     "layer": "kernels", "moves": "tpot_p90_ms",
                     "workloads": [CELL]}
    assert manifest["per_layer"][-1] is entry
