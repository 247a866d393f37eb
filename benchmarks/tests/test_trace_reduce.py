"""The trace reduction, on a hand-made trace whose answers are known and on
small recorded traces of the two cells (the first tenth of a second of a
traced window of a chip run of PR 25, as `load_events` gives it)."""
import json
import os

import pytest
from conftest import HERE

import trace_reduce as tr


def hand_made():
    ops = [
        ("while.1", 0.0, 6.0),                       # a loop holding two ops
        ("flash_decode_decode_f32.9 f32[4]", 1.0, 2.0),
        ("fusion.3 f32[8]", 3.5, 1.5),
        ("copy.2 f32[8]", 8.0, 1.0),
        ("all-gather.1 f32[8]", 9.0, 2.0),           # half of it under compute
        ("fusion.4 f32[8]", 10.0, 2.0),
    ]
    modules = [("jit__decode_fn(1)", 0.0, 6.0), ("jit__chunk_fn(2)", 8.0, 4.0)]
    return {"devices": {0: {"ops": ops, "modules": modules}},
            "marks": [("bench/window", 0.0, 12.0), ("bench/scheduler.run", 0.0, 12.0)]}


def test_hand_made_trace():
    ev = hand_made()
    lo, hi = tr.window_of(ev)
    assert (lo, hi) == (0.0, 12.0)
    assert tr.busy_seconds(ev, lo, hi) == pytest.approx(10.0)   # idle 6..8 only
    seconds, calls = tr.op_seconds(ev, lo, hi, r"flash_decode_decode_")
    assert (seconds, calls) == (2.0, 1)
    seconds, calls = tr.op_seconds(ev, lo, hi, r"jit__decode_fn", line="modules")
    assert (seconds, calls) == (6.0, 1)
    top = dict(tr.top_ops(ev, lo, hi))
    assert top["while.1"] == pytest.approx(2.5)                  # 6 less 2 less 1.5
    assert top["flash_decode_decode_f32.9 f32[4]"] == pytest.approx(2.0)
    gaps = tr.idle_gaps(ev, lo, hi)
    assert gaps == [["jit__decode_fn -> jit__chunk_fn [bench/scheduler.run]",
                     pytest.approx(2.0)]]
    # a window clipped in the middle of an op counts only the part inside
    assert tr.busy_seconds(ev, 5.0, 9.0) == pytest.approx(2.0)


def test_short_names():
    assert tr.short_name("%copy.12 = f32[65,24,64]{2,1,0:T(8,128)} copy(f32[65] %x)") == "copy.12 f32[65,24,64]"
    assert tr.short_name("%while.5 = (s32[]{:T(128)}, f32[16]) while(...)") == "while.5"
    assert tr.short_name("jit__decode_fn(123)") == "jit__decode_fn(123)"


def recorded(name):
    path = os.path.join(HERE, name)
    if not os.path.isfile(path):
        pytest.skip(f"{name} is not recorded")
    with open(path) as f:
        raw = json.load(f)
    events = {"devices": {int(k): {line: [tuple(e) for e in evs]
                                   for line, evs in v.items()}
                          for k, v in raw["devices"].items()},
              "marks": [tuple(m) for m in raw["marks"]]}
    return events, raw["window"]


def test_recorded_serve_trace():
    events, (lo, hi) = recorded("serve_trace_fixture.json")
    busy = tr.busy_seconds(events, lo, hi)
    assert 0 < busy <= hi - lo
    kernel, calls = tr.op_seconds(events, lo, hi, r"flash_decode_decode_")
    program, steps = tr.op_seconds(events, lo, hi, r"jit__decode_fn", line="modules")
    assert calls >= 24 and steps >= 1            # one kernel call per layer per step
    assert 0 < kernel < program <= busy + 1e-9
    own = sum(sec for _, sec in tr.top_ops(events, lo, hi, k=10**6))
    assert own == pytest.approx(busy, rel=0.02)  # self times add up to busy time


def test_recorded_train_trace():
    events, (lo, hi) = recorded("train_trace_fixture.json")
    busy = tr.busy_seconds(events, lo, hi)
    assert 0 < busy <= hi - lo
    _, fwd = tr.op_seconds(events, lo, hi, r"flash_attention_fwd")
    _, dq = tr.op_seconds(events, lo, hi, r"flash_attention_bwd_dq")
    assert fwd >= 1 and dq >= 1
